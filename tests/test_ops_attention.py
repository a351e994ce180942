"""Flash attention kernel parity tests.

Mirrors the reference's attention test strategy
(``apex/contrib/test/fmha/test_fmha.py``: fused kernel vs a pure-python
reference over padded varlen batches; ``apex/contrib/test/multihead_attn``:
fused vs unfused module outputs/grads).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

os.environ.setdefault("APEX_TPU_FORCE_PALLAS", "interpret")

from apex_tpu.ops.attention import _mha_reference, flash_attention  # noqa: E402


def _rand(shape, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk", [(64, 64), (100, 300), (257, 257)])
def test_forward_matches_reference(causal, sq, sk):
    q = _rand((2, 3, sq, 64), seed=1)
    k = _rand((2, 3, sk, 64), seed=2)
    v = _rand((2, 3, sk, 64), seed=3)
    out = flash_attention(q, k, v, causal=causal)
    ref = _mha_reference(q, k, v, None, 1.0 / np.sqrt(64), causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_varlen_kv_lengths(causal):
    # second batch element's valid length (37) is below the k-block size, so
    # whole k-blocks are fully masked — the fmha padded-batch case
    # (apex/contrib/fmha/fmha.py:41-56)
    q = _rand((2, 2, 96, 64), seed=1)
    k = _rand((2, 2, 300, 64), seed=2)
    v = _rand((2, 2, 300, 64), seed=3)
    lens = jnp.asarray([300, 37], jnp.int32)
    out = flash_attention(q, k, v, causal=causal, kv_lengths=lens)
    ref = _mha_reference(q, k, v, lens, 1.0 / np.sqrt(64), causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("lens", [None, (300, 37)])
def test_backward_matches_reference(causal, lens):
    q = _rand((2, 2, 96, 64), seed=4)
    k = _rand((2, 2, 300, 64), seed=5)
    v = _rand((2, 2, 300, 64), seed=6)
    kvl = None if lens is None else jnp.asarray(lens, jnp.int32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, kv_lengths=kvl)
        return jnp.sum(o.astype(jnp.float32) * jnp.cos(o.astype(jnp.float32)))

    def loss_ref(q, k, v):
        o = _mha_reference(q, k, v, kvl, 1.0 / np.sqrt(64), causal)
        return jnp.sum(o.astype(jnp.float32) * jnp.cos(o.astype(jnp.float32)))

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_cross_attention_offset():
    # sq != sk causal: the last q row attends to everything, row 0 attends to
    # the first sk - sq + 1 keys (the standard offset convention)
    q = _rand((1, 1, 4, 64), seed=7)
    k = _rand((1, 1, 10, 64), seed=8)
    v = _rand((1, 1, 10, 64), seed=9)
    out = flash_attention(q, k, v, causal=True)
    ref = _mha_reference(q, k, v, None, 1.0 / np.sqrt(64), True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_jit_and_scale():
    q = _rand((1, 2, 128, 32), seed=1)
    k = _rand((1, 2, 128, 32), seed=2)
    v = _rand((1, 2, 128, 32), seed=3)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, softmax_scale=0.5))
    out = f(q, k, v)
    ref = _mha_reference(q, k, v, None, 0.5, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


class TestGQA:
    """Grouped-query / multi-query attention (kv_heads divides heads): the
    kernel reads shared K/V blocks per group — parity vs the broadcast
    reference, forward and backward."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("kv_heads", [1, 2])   # MQA and GQA
    def test_forward(self, causal, kv_heads):
        q = _rand((2, 4, 96, 64), seed=11)
        k = _rand((2, kv_heads, 160, 64), seed=12)
        v = _rand((2, kv_heads, 160, 64), seed=13)
        out = flash_attention(q, k, v, causal=causal)
        ref = _mha_reference(q, k, v, None, 1.0 / np.sqrt(64), causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_backward(self, causal):
        q = _rand((2, 4, 64, 64), seed=14)
        k = _rand((2, 2, 128, 64), seed=15)
        v = _rand((2, 2, 128, 64), seed=16)

        def loss(fn):
            def inner(q, k, v):
                o = fn(q, k, v)
                return jnp.sum(o * jnp.sin(o))
            return inner

        g = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(lambda q, k, v: _mha_reference(
            q, k, v, None, 1.0 / np.sqrt(64), causal)),
            argnums=(0, 1, 2))(q, k, v)
        assert g[1].shape == k.shape     # dk has kv_heads, not heads
        for a, b in zip(g, gr):
            # atol 2e-4: on real TPU a handful of elements differ at ~1e-4
            # from fp32 accumulation ORDER (block-wise vs full-row sums),
            # even at highest matmul precision
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=5e-5)

    def test_varlen_gqa(self):
        q = _rand((2, 4, 64, 64), seed=17)
        k = _rand((2, 1, 200, 64), seed=18)
        v = _rand((2, 1, 200, 64), seed=19)
        lens = jnp.asarray([200, 23], jnp.int32)
        out = flash_attention(q, k, v, kv_lengths=lens)
        ref = _mha_reference(q, k, v, lens, 1.0 / np.sqrt(64), False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_indivisible_heads_rejected(self):
        q = _rand((1, 3, 32, 64))
        k = _rand((1, 2, 32, 64))
        with pytest.raises(ValueError, match="divide"):
            flash_attention(q, k, k)


class TestSlidingWindow:
    """Mistral-class local attention: keep the last ``window`` keys per
    query; far-past K blocks are skipped in the kernel."""

    @pytest.mark.parametrize("window", [1, 16, 100, 1000])
    def test_forward(self, window):
        q = _rand((2, 2, 300, 64), seed=21)
        k = _rand((2, 2, 300, 64), seed=22)
        v = _rand((2, 2, 300, 64), seed=23)
        out = flash_attention(q, k, v, causal=True, sliding_window=window)
        ref = _mha_reference(q, k, v, None, 1.0 / np.sqrt(64), True, window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_backward(self):
        q = _rand((1, 2, 160, 64), seed=24)
        k = _rand((1, 2, 160, 64), seed=25)
        v = _rand((1, 2, 160, 64), seed=26)

        def loss(fn):
            return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

        g = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, sliding_window=48)),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(lambda q, k, v: _mha_reference(
            q, k, v, None, 1.0 / np.sqrt(64), True, 48)),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            # atol 2e-4: TPU fp32 accumulation-order noise (see TestGQA)
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=5e-5)

    def test_gqa_window(self):
        q = _rand((1, 4, 128, 64), seed=27)
        k = _rand((1, 2, 128, 64), seed=28)
        v = _rand((1, 2, 128, 64), seed=29)
        out = flash_attention(q, k, v, causal=True, sliding_window=32)
        ref = _mha_reference(q, k, v, None, 1.0 / np.sqrt(64), True, 32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_window_one_attends_self_only(self):
        q = _rand((1, 1, 32, 64), seed=30)
        k = _rand((1, 1, 32, 64), seed=31)
        v = _rand((1, 1, 32, 64), seed=32)
        out = flash_attention(q, k, v, causal=True, sliding_window=1)
        # softmax over a single key == that key's value
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(v, np.float32),
                                   atol=2e-5, rtol=2e-5)

    def test_requires_causal(self):
        q = _rand((1, 1, 32, 64))
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, q, q, sliding_window=8)


class TestBandedWindowGrid:
    """The sliding-window banded grid (static-offset fast path): the k/q
    grid axes only walk blocks near the window diagonal. These sizes force
    multiple blocks and nonzero band bases (seq >> block), pinning the
    band-base arithmetic, the nk_grid/nq_grid sizing, and the edge clamps
    that single-block tests never reach."""

    @pytest.mark.parametrize("window", [1, 130, 200, 1000])
    def test_fwd_parity_multiblock(self, window):
        q = _rand((1, 2, 1024, 32), seed=1)
        k = _rand((1, 2, 1024, 32), seed=2)
        v = _rand((1, 2, 1024, 32), seed=3)
        out = flash_attention(q, k, v, causal=True, sliding_window=window,
                              block_q=128, block_k=256)
        ref = _mha_reference(q, k, v, None, 1.0 / np.sqrt(32), True, window)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=2e-2, atol=2e-2)

    def test_bwd_parity_multiblock(self):
        q = _rand((1, 2, 768, 32), seed=4)
        k = _rand((1, 2, 768, 32), seed=5)
        v = _rand((1, 2, 768, 32), seed=6)

        def loss(fn):
            return lambda q, k, v: jnp.sum(
                fn(q, k, v).astype(jnp.float32) ** 2)

        g_new = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, sliding_window=200,
            block_q=128, block_k=128)), argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss(lambda q, k, v: _mha_reference(
            q, k, v, None, 1.0 / np.sqrt(32), True, 200)),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_new, g_ref):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=5e-2, atol=5e-2)

    def test_cross_attention_offset_band(self):
        # sk > sq: queries sit at the end; the band base includes the
        # static sk-sq offset
        q = _rand((1, 2, 256, 32), seed=7)
        k = _rand((1, 2, 1024, 32), seed=8)
        v = _rand((1, 2, 1024, 32), seed=9)
        out = flash_attention(q, k, v, causal=True, sliding_window=300,
                              block_q=128, block_k=128)
        ref = _mha_reference(q, k, v, None, 1.0 / np.sqrt(32), True, 300)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# packed-QKV (layout-native) path
# ---------------------------------------------------------------------------

from apex_tpu.ops.attention import (  # noqa: E402
    flash_attention_packed,
    packed_attention_supported,
    packed_geometry,
)


def _pack_qkv(q, k, v, qpg, d):
    """[b,h,s,d] triple -> the ParallelAttention packed [s, b, W] layout
    (per group: q_0..q_{qpg-1} | k | v along the column dim)."""
    b, h, s, _ = q.shape
    g = h // qpg
    q5 = q.transpose(2, 0, 1, 3).reshape(s, b, g, qpg, d)
    k5 = k.transpose(2, 0, 1, 3)[:, :, :, None]
    v5 = v.transpose(2, 0, 1, 3)[:, :, :, None]
    return jnp.concatenate([q5, k5, v5], axis=3).reshape(s, b, -1)


class TestPackedQKV:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("g,qpg", [
        pytest.param(4, 1, marks=pytest.mark.slow), (2, 2), (1, 4)])
    def test_fwd_bwd_matches_unpacked(self, causal, g, qpg):
        s, b, d = 128, 2, 64
        h = g * qpg
        q = _rand((b, h, s, d), seed=11)
        k = _rand((b, g, s, d), seed=12)
        v = _rand((b, g, s, d), seed=13)
        qkv = _pack_qkv(q, k, v, qpg, d)

        def packed_loss(qkv):
            o = flash_attention_packed(qkv, queries_per_group=qpg,
                                       head_dim=d, causal=causal)
            return jnp.sum(o.astype(jnp.float32) ** 2), o

        def ref_loss(qkv):
            # unpack exactly as the packed kernel sees it
            qkv5 = qkv.reshape(s, b, g, qpg + 2, d)
            qq = qkv5[:, :, :, :qpg].reshape(s, b, h, d).transpose(1, 2, 0, 3)
            kk = qkv5[:, :, :, qpg].transpose(1, 2, 0, 3)
            vv = qkv5[:, :, :, qpg + 1].transpose(1, 2, 0, 3)
            o4 = _mha_reference(qq, kk, vv, None, 1.0 / np.sqrt(d), causal)
            o = o4.transpose(2, 0, 1, 3).reshape(s, b, h * d)
            return jnp.sum(o.astype(jnp.float32) ** 2), o

        (_, op), gp = jax.value_and_grad(packed_loss, has_aux=True)(qkv)
        (_, orf), gr = jax.value_and_grad(ref_loss, has_aux=True)(qkv)
        np.testing.assert_allclose(np.asarray(op), np.asarray(orf),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   rtol=2e-3, atol=2e-3)

    @pytest.mark.slow  # varlen/window parity sweep: slow tier (ROADMAP)

    def test_varlen_and_window(self):
        s, b, g, qpg, d = 256, 3, 2, 1, 64
        qkv = _rand((s, b, g * (qpg + 2) * d), seed=21)
        kvl = jnp.asarray([256, 100, 3], jnp.int32)
        for kwargs in ({"kv_lengths": kvl},
                       {"causal": True, "sliding_window": 50}):
            def packed_loss(qkv):
                o = flash_attention_packed(qkv, queries_per_group=qpg,
                                           head_dim=d, **kwargs)
                return jnp.sum(o.astype(jnp.float32) ** 2)

            def ref_loss(qkv):
                qkv5 = qkv.reshape(s, b, g, qpg + 2, d)
                qq = qkv5[:, :, :, 0].transpose(1, 2, 0, 3)
                kk = qkv5[:, :, :, 1].transpose(1, 2, 0, 3)
                vv = qkv5[:, :, :, 2].transpose(1, 2, 0, 3)
                o4 = _mha_reference(qq, kk, vv, kwargs.get("kv_lengths"),
                                    1.0 / np.sqrt(d),
                                    kwargs.get("causal", False),
                                    kwargs.get("sliding_window"))
                return jnp.sum(o4.astype(jnp.float32) ** 2)

            gp = jax.grad(packed_loss)(qkv)
            gr = jax.grad(ref_loss)(qkv)
            np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                       rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("s,causal", [
        (100, True), pytest.param(197, False, marks=pytest.mark.slow)])
    def test_ragged_s_pads_internally(self, s, causal):
        # ViT-class lengths (197 = 196 patches + CLS): rows pad to the
        # sublane multiple, padded keys masked via kv_lengths, padded
        # query rows sliced off
        b, g, qpg, d = 2, 4, 1, 64
        qkv = _rand((s, b, g * (qpg + 2) * d), seed=61)

        def packed_loss(qkv):
            o = flash_attention_packed(qkv, queries_per_group=qpg,
                                       head_dim=d, causal=causal)
            assert o.shape == (s, b, g * qpg * d)
            return jnp.sum(o.astype(jnp.float32) ** 2), o

        def ref_loss(qkv):
            qkv5 = qkv.reshape(s, b, g, qpg + 2, d)
            qq = qkv5[:, :, :, 0].transpose(1, 2, 0, 3)
            kk = qkv5[:, :, :, 1].transpose(1, 2, 0, 3)
            vv = qkv5[:, :, :, 2].transpose(1, 2, 0, 3)
            o4 = _mha_reference(qq, kk, vv, None, 1.0 / np.sqrt(d), causal)
            o = o4.transpose(2, 0, 1, 3).reshape(s, b, g * d)
            return jnp.sum(o.astype(jnp.float32) ** 2), o

        (_, op), gp = jax.value_and_grad(packed_loss, has_aux=True)(qkv)
        (_, orf), gr = jax.value_and_grad(ref_loss, has_aux=True)(qkv)
        np.testing.assert_allclose(np.asarray(op), np.asarray(orf),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   rtol=2e-3, atol=2e-3)

    def test_geometry_gate(self):
        # d=64, qpg odd -> two groups per cell; odd group count unsupported
        assert packed_geometry(16, 1, 64) == (2, 384, 128)
        assert packed_geometry(3, 1, 64) is None
        assert packed_geometry(4, 2, 64) == (1, 256, 128)
        assert packed_geometry(2, 1, 128) == (1, 384, 128)
        # s gating: anything up to 1024 (ragged s pads to the sublane
        # multiple internally); beyond that the (s, s) block leaves VMEM
        assert packed_attention_supported(1024, 16, 1, 64)
        assert packed_attention_supported(1000, 16, 1, 64)
        assert packed_attention_supported(197, 16, 1, 64)
        assert not packed_attention_supported(2048, 16, 1, 64)


class TestFusedMultiblockBackward:
    """The fused one-pass dq/dk/dv kernel (non-banded nq >= 2 shapes) —
    small explicit blocks force real multi-block grids so the aliased
    fp32 dq accumulation, dead-block passthrough and scratch flushes run
    for every grid transition the dispatch condition allows.

    The fused kernel's dq accumulation is a compiled Mosaic window-DMA
    mechanism that the Pallas interpreter cannot model (it reads inputs
    functionally, ignoring input_output_aliases), so under the default
    interpret-mode suite these shapes take the two-kernel path and this
    class pins THAT parity; under ``APEX_TPU_TEST_TPU=1`` on hardware the
    same tests compile and pin the fused kernel itself."""

    def _grads(self, q, k, v, kvl=None, causal=True, bq=128, bk=128):
        def loss(fn):
            return lambda q, k, v: jnp.sum(
                fn(q, k, v).astype(jnp.float32) ** 2)
        g_new = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, kv_lengths=kvl,
            block_q=bq, block_k=bk)), argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss(lambda q, k, v: _mha_reference(
            q, k, v, kvl, 1.0 / np.sqrt(q.shape[-1]), causal)),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_new, g_ref):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("causal", [False, True])
    def test_2x2_grid(self, causal):
        # nq = nk = 2: dq blocks revisited across the outer j dim — the
        # aliased read-modify-write accumulation path
        q = _rand((2, 3, 256, 64), seed=31)
        k = _rand((2, 3, 256, 64), seed=32)
        v = _rand((2, 3, 256, 64), seed=33)
        self._grads(q, k, v, causal=causal)

    @pytest.mark.slow
    def test_causal_dead_blocks_4x4(self):
        # nq = nk = 4: 6 of 16 blocks are causally dead — their steps
        # must pass dq through unchanged (a dropped write loses a j
        # contribution; a stale write corrupts a neighbor block)
        q = _rand((1, 2, 512, 64), seed=34)
        k = _rand((1, 2, 512, 64), seed=35)
        v = _rand((1, 2, 512, 64), seed=36)
        self._grads(q, k, v, causal=True)

    @pytest.mark.slow
    def test_gqa_group_sweep(self):
        # grouped heads extend the inner t sweep; dk/dv scratch must
        # accumulate across the whole (g, i) walk before flushing
        q = _rand((2, 4, 256, 64), seed=37)
        k = _rand((2, 2, 256, 64), seed=38)
        v = _rand((2, 2, 256, 64), seed=39)
        self._grads(q, k, v, causal=True)

    @pytest.mark.slow
    def test_varlen(self):
        q = _rand((2, 2, 256, 64), seed=40)
        k = _rand((2, 2, 256, 64), seed=41)
        v = _rand((2, 2, 256, 64), seed=42)
        self._grads(q, k, v, causal=False,
                    kvl=jnp.asarray([200, 37], jnp.int32))

    @pytest.mark.slow  # cross-shape parity sweep: slow tier (ROADMAP)

    def test_cross_shapes(self):
        # sq != sk, including the nk == 1 single-j fused case and the
        # nq == 1 shape that must take the two-kernel fallback
        for sq, sk in [(256, 512), (384, 128), (128, 512)]:
            q = _rand((1, 2, sq, 64), seed=43 + sq)
            k = _rand((1, 2, sk, 64), seed=44 + sk)
            v = _rand((1, 2, sk, 64), seed=45 + sk)
            self._grads(q, k, v, causal=True)


class TestPackedRope:
    """In-kernel RoPE on the packed path vs rotate-then-flash on the 4D
    path — forward and the un-rotated dqkv cotangent, full and partial
    rotary dims."""

    @pytest.mark.parametrize("rot", [
        64, pytest.param(32, marks=pytest.mark.slow)])
    def test_rope_parity(self, rot):
        from apex_tpu.ops.rope import fused_rope
        s, b, g, qpg, d = 128, 2, 4, 1, 64
        qkv = _rand((s, b, g * (qpg + 2) * d), seed=51)
        inv = 1.0 / 10000.0 ** (np.arange(0, rot, 2, dtype=np.float32)
                                / rot)
        f = np.arange(s, dtype=np.float32)[:, None] * inv[None, :]
        freqs = jnp.asarray(np.concatenate([f, f], axis=-1))   # [s, rot]

        def packed_loss(qkv):
            o = flash_attention_packed(qkv, queries_per_group=qpg,
                                       head_dim=d, causal=True,
                                       rope_freqs=freqs)
            return jnp.sum(o.astype(jnp.float32) ** 2), o

        def ref_loss(qkv):
            qkv5 = qkv.reshape(s, b, g, qpg + 2, d)
            qq = qkv5[:, :, :, 0]                        # [s, b, g, d]
            kk = qkv5[:, :, :, 1]
            vv = qkv5[:, :, :, 2].transpose(1, 2, 0, 3)
            f4 = freqs.reshape(s, 1, 1, rot)
            qq = fused_rope(qq, f4).transpose(1, 2, 0, 3)
            kk = fused_rope(kk, f4).transpose(1, 2, 0, 3)
            o4 = _mha_reference(qq, kk, vv, None, 1.0 / np.sqrt(d), True)
            o = o4.transpose(2, 0, 1, 3).reshape(s, b, g * d)
            return jnp.sum(o.astype(jnp.float32) ** 2), o

        (_, op), gp = jax.value_and_grad(packed_loss, has_aux=True)(qkv)
        (_, orf), gr = jax.value_and_grad(ref_loss, has_aux=True)(qkv)
        np.testing.assert_allclose(np.asarray(op), np.asarray(orf),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   rtol=5e-3, atol=5e-3)


class TestPackedDropout:
    """In-kernel attention dropout on the packed path (the reference fmha
    capability). The mask is a position-deterministic hash shared by the
    kernels, interpret mode and the XLA fallback, so every test here —
    including the exact-mask parity check — runs on all backends."""

    def test_rate_zero_is_exact_noop(self):
        s, b, g, qpg, d = 128, 2, 4, 1, 64
        qkv = _rand((s, b, g * (qpg + 2) * d), seed=71)
        o0 = flash_attention_packed(qkv, queries_per_group=qpg, head_dim=d,
                                    causal=True)
        o1 = flash_attention_packed(qkv, queries_per_group=qpg, head_dim=d,
                                    causal=True, dropout_rate=0.0,
                                    dropout_seed=jnp.asarray([3], jnp.int32))
        np.testing.assert_array_equal(np.asarray(o0), np.asarray(o1))

    @pytest.mark.slow
    def test_fallback_dropout_statistics(self):
        # CPU/interpret route: jax.random dropout on materialized probs —
        # unbiased in expectation and deterministic per seed
        s, b, g, qpg, d = 128, 2, 2, 1, 64
        qkv = _rand((s, b, g * (qpg + 2) * d), seed=72)
        kw = dict(queries_per_group=qpg, head_dim=d, causal=False)
        o_ref = flash_attention_packed(qkv, **kw).astype(jnp.float32)
        outs = [flash_attention_packed(
            qkv, dropout_rate=0.3,
            dropout_seed=jnp.asarray([i], jnp.int32), **kw)
            .astype(jnp.float32) for i in range(24)]
        same = flash_attention_packed(
            qkv, dropout_rate=0.3, dropout_seed=jnp.asarray([0], jnp.int32),
            **kw)
        np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(same))
        mean = jnp.stack(outs).mean(0)
        err = float(jnp.mean(jnp.abs(mean - o_ref))
                    / (jnp.mean(jnp.abs(o_ref)) + 1e-9))
        assert err < 0.25, f"dropout mean deviates {err:.3f} from no-drop"

    def test_kernel_dropout_exact_vs_hash_mask(self):
        """The dropout mask is a position-deterministic hash, so the
        expected mask is computable OUTSIDE the kernel: replay attention
        with that exact mask in plain XLA and demand fwd AND grads match
        the packed path — proving the forward mask, the backward's
        regenerated mask, and the dropout VJP algebra all agree."""
        from apex_tpu.ops.attention import _hash_keep, packed_geometry

        s, b, g, qpg, d = 128, 2, 4, 1, 64
        rate = 0.3
        seed = jnp.asarray([12345], jnp.int32)
        qkv = _rand((s, b, g * (qpg + 2) * d), seed=73, dtype=jnp.float32)
        h_tot = g * qpg
        from apex_tpu.ops.attention import _drop_combo
        combo = _drop_combo(
            jnp.arange(b, dtype=jnp.uint32)[:, None, None, None],
            jnp.arange(h_tot, dtype=jnp.uint32)[None, :, None, None])
        keep = _hash_keep(seed.reshape(()), combo, (b, h_tot, s, s), rate)

        def packed_loss(qkv):
            o = flash_attention_packed(
                qkv, queries_per_group=qpg, head_dim=d, causal=True,
                dropout_rate=rate, dropout_seed=seed)
            return jnp.sum(o.astype(jnp.float32) ** 2), o

        def ref_loss(qkv):
            qkv5 = qkv.reshape(s, b, g, qpg + 2, d)
            qq = qkv5[:, :, :, 0].transpose(1, 2, 0, 3)
            kk = qkv5[:, :, :, 1].transpose(1, 2, 0, 3)
            vv = qkv5[:, :, :, 2].transpose(1, 2, 0, 3)
            sm = jnp.einsum("bhqd,bhkd->bhqk", qq, kk) / np.sqrt(d)
            row = jnp.arange(s)[:, None]
            col = jnp.arange(s)[None, :]
            sm = jnp.where(col <= row, sm, -1e30)
            p = jax.nn.softmax(sm, axis=-1)
            p = jnp.where(keep, p / (1.0 - rate), 0.0)
            o4 = jnp.einsum("bhqk,bhkd->bhqd", p, vv)
            o = o4.transpose(2, 0, 1, 3).reshape(s, b, g * d)
            return jnp.sum(o.astype(jnp.float32) ** 2), o

        (_, op), gp = jax.value_and_grad(packed_loss, has_aux=True)(qkv)
        (_, orf), gr = jax.value_and_grad(ref_loss, has_aux=True)(qkv)
        np.testing.assert_allclose(np.asarray(op), np.asarray(orf),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   rtol=2e-3, atol=2e-3)

    def test_hash_mask_statistics(self):
        from apex_tpu.ops.attention import _hash_keep
        keep = _hash_keep(jnp.uint32(7), jnp.uint32(3), (512, 512), 0.3)
        frac = float(jnp.mean(keep.astype(jnp.float32)))
        assert abs(frac - 0.7) < 0.01, frac
        # rows/cols must not be degenerate (per-row keep rate spread)
        rowfrac = jnp.mean(keep.astype(jnp.float32), axis=1)
        assert float(jnp.std(rowfrac)) < 0.05
