"""``chip_smoke.py`` on the CPU: its phases at a tiny config with
interpret-mode kernels, its device gate, the compile-cache helper, the
peak table's refusal to guess — and the decode kernel through the REAL
Mosaic compiler (libtpu compiles for a described v5e topology without a
chip), which is what interpret mode cannot see."""

import os

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from apex_tpu.models import TransformerConfig
from apex_tpu.ops import _support, decode_attention, grouped_matmul
from apex_tpu.serving import EngineConfig
from apex_tpu.utils import compile_cache
from apex_tpu.utils.flops import peak_flops_per_chip

TINY = TransformerConfig(
    num_layers=2, hidden_size=64, num_attention_heads=4, vocab_size=128,
    max_position_embeddings=64, hidden_dropout=0.0, attention_dropout=0.0,
    compute_dtype=jnp.bfloat16)


def test_main_refuses_cpu_before_building_anything(monkeypatch, capsys):
    def no_build(*a, **k):
        raise AssertionError("built a model on a CPU backend")

    monkeypatch.setattr(chip_smoke, "gpt2_124m", no_build)
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code not in (0, None)
    assert "cpu" in str(exc.value.code)
    assert capsys.readouterr().out == ""        # no result line


def test_last_stdout_line_is_the_bare_verdict(monkeypatch, capsys):
    """The driver parses the LAST stdout line: one JSON object with
    exactly ``ok`` and ``device`` {platform, kind, count}. Phases are
    stubbed; this pins main()'s output contract only."""
    import json

    import benchmarks._harness as harness

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(harness, "start", lambda: dict(device))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "tpu")
    _support.pallas_mode.cache_clear()

    class Ledger:
        def summary(self):
            return {}

    monkeypatch.setattr(chip_smoke, "_CompileLedger", Ledger)
    monkeypatch.setattr(chip_smoke, "seeded_model", lambda cfg: (None, None))
    monkeypatch.setattr(chip_smoke, "train_phase", lambda *a, **k: {
        "first_loss": 2.0, "last_loss": 1.0, "tpu_custom_calls": 1})
    monkeypatch.setattr(chip_smoke, "kernel_phase", lambda **k: {})
    monkeypatch.setattr(chip_smoke, "serve_phase",
                        lambda *a, **k: {"tpu_custom_calls": 1})
    try:
        assert chip_smoke.main() == 0
    finally:
        _support.pallas_mode.cache_clear()
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    assert lines[-2].startswith("chip_smoke: summary ")
    summary = json.loads(lines[-2].split("summary ", 1)[1])
    assert summary["multichip"] == "not run: 1 device(s)"
    assert list(summary)[-1] == "claim" and summary["claim"] is None


def test_train_phase_tiny(pallas_kernels):
    out = chip_smoke.train_phase(TINY, batch=4, seq=32, steps=5)
    assert out["last_loss"] < out["first_loss"]
    assert out["tpu_custom_calls"] == 0         # interpreted, not Mosaic


def test_serve_phase_tiny(pallas_kernels):
    engine_cfg = EngineConfig(max_slots=4, max_len=64, page_size=8)
    out = chip_smoke.serve_phase(
        *chip_smoke.seeded_model(TINY), engine_cfg,
        # two prefill buckets (8, 32); #3 hits #2's 16-token prefix
        lambda: chip_smoke.build_requests(
            TINY.vocab_size, (5, 7, 20, 24, 6, 8), (6, 8, 5, 7, 4, 10),
            16, (2, 3)),
        generate_checks=1)
    assert out["requests"] == 6 and out["tokens"] == 40
    assert out["prefix_hits"] >= 1
    assert out["bare_vs_supervised_identical"] == "6/6"


@pytest.mark.slow   # kernel-vs-reference itself is tier-1 in
#                     test_serving_paged / test_spec_quant; this only
#                     drives the phase function's own plumbing
def test_kernel_phase_tiny(pallas_kernels):
    errors = chip_smoke.kernel_phase(
        slots=4, heads=4, head_dim=16, page_size=8, pages_per_slot=8,
        dtype=jnp.bfloat16)["max_abs_err"]
    assert set(errors) == {"bf16", "bf16_w3", "int8", "int8_w3", "latent"}


def test_compile_cache_is_placeable_and_fixed(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert compile_cache.enable_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == before   # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first = compile_cache.enable_compile_cache()
        assert first == compile_cache.enable_compile_cache()
        root = os.path.dirname(os.path.abspath(chip_smoke.__file__))
        assert first == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_peak_flops_refuses_to_guess():
    class Fake:
        platform, device_kind = "tpu", "TPU v5x"

    assert peak_flops_per_chip() is None        # cpu: MFU has no meaning
    with pytest.raises(ValueError, match="TPU v5x"):
        peak_flops_per_chip(Fake())
    Fake.device_kind = "TPU v5 lite"
    assert peak_flops_per_chip(Fake()) == 197e12


@pytest.mark.parametrize("window,quantized", [(1, False), (3, False),
                                              (1, True), (3, True)])
def test_decode_kernel_compiles_under_mosaic(monkeypatch, window, quantized):
    """GPT-2 124M widths (12 heads x 64, page 64) through libtpu's Mosaic
    compiler for a described v5e — no chip needed. Interpret mode accepts
    reshapes, transposes and block shapes the real compiler refuses."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    if jax.default_backend() != "cpu":
        pytest.skip("a chip run compiles the kernel for real")
    monkeypatch.setenv("TPU_SKIP_MDS_QUERY", "1")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1), num_slices=1)
    except Exception as e:   # no libtpu in this environment
        pytest.skip(f"no TPU topology description available: {e}")
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "tpu")
    _support.pallas_mode.cache_clear()
    on = SingleDeviceSharding(topo.devices[0])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on)

    b, heads, dh, ps, pps, n_pages = 8, 12, 64, 64, 16, 130
    f = heads * dh
    pool = arg((n_pages, ps, f), jnp.int8 if quantized else jnp.bfloat16)
    scales = arg((n_pages, heads), jnp.float32) if quantized else None
    try:
        compiled = decode_attention._pallas.lower(
            arg((b, window, heads, dh), jnp.bfloat16),
            arg((b, window, f), jnp.bfloat16),
            arg((b, window, f), jnp.bfloat16), pool, pool, scales, scales,
            arg((b, pps), jnp.int32), arg((b,), jnp.int32),
            group=1, sliding_window=None).compile()
    finally:
        _support.pallas_mode.cache_clear()
    assert "tpu_custom_call" in compiled.as_text()


def _described_v5e(monkeypatch):
    """A sharding on a described (not attached) v5e chip, with the Pallas
    paths pinned to the real compiler; skips where libtpu cannot describe
    one. Call from inside a test only (one process may hold libtpu)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    if jax.default_backend() != "cpu":
        pytest.skip("a chip run compiles the kernel for real")
    monkeypatch.setenv("TPU_SKIP_MDS_QUERY", "1")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1), num_slices=1)
    except Exception as e:   # no libtpu in this environment
        pytest.skip(f"no TPU topology description available: {e}")
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "tpu")
    _support.pallas_mode.cache_clear()
    return SingleDeviceSharding(topo.devices[0])


#: sha256[:16] of the decode kernels' calls as jaxprs (kernel bodies
#: included), read where each was measured on the chip: the K/V kernel at
#: PR 33's tree (8cb1cae), the latent kernel at PR 34's first tree. The
#: kernels share their page walk (``_PageWalk``) since PR 34; an edit that
#: keeps these keeps the measured kernels op for op, and one that moves
#: them wants a chip run.
MEASURED_KERNELS = {
    "gpt2m": "e2b03a570c069802",
    "trinity-window": "2eb671714e0c29e0",
    "int8-w3": "a6d0292bf3778012",
    "latent": "1ba8e3fa9289f559",
}


@pytest.mark.parametrize("which", sorted(MEASURED_KERNELS))
def test_decode_kernels_trace_to_the_measured_programs(monkeypatch, which):
    import hashlib

    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "tpu")
    _support.pallas_mode.cache_clear()

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    try:
        if which == "latent":
            b, heads, rank, lanes, ps, pps = 64, 128, 512, 128, 64, 128
            jaxpr = jax.make_jaxpr(
                lambda *a: decode_attention._latent_pallas.__wrapped__(
                    *a, scale=0.0781))(
                arg((b, heads, rank + lanes), jnp.bfloat16),
                arg((b, rank), jnp.bfloat16), arg((b, lanes), jnp.bfloat16),
                arg((b * pps, ps, rank), jnp.bfloat16),
                arg((b * pps, ps, lanes), jnp.bfloat16),
                arg((b, pps), jnp.int32), arg((b,), jnp.int32))
        else:
            b, w, heads, kvh, dh, ps, pps, window, dtype = {
                "gpt2m": (96, 1, 16, 16, 64, 64, 16, None, jnp.bfloat16),
                "trinity-window": (96, 1, 32, 4, 128, 64, 64, 2048,
                                   jnp.bfloat16),
                "int8-w3": (8, 3, 16, 16, 64, 64, 16, None, jnp.int8),
            }[which]
            f = kvh * dh
            pool = arg((b * pps, ps, f), dtype)
            scales = (arg((b * pps, kvh), jnp.float32)
                      if dtype == jnp.int8 else None)
            jaxpr = jax.make_jaxpr(
                lambda *a: decode_attention._pallas.__wrapped__(
                    *a, group=heads // kvh, sliding_window=window))(
                arg((b, w, heads, dh), jnp.bfloat16),
                arg((b, w, f), jnp.bfloat16), arg((b, w, f), jnp.bfloat16),
                pool, pool, scales, scales, arg((b, pps), jnp.int32),
                arg((b,), jnp.int32))
    finally:
        _support.pallas_mode.cache_clear()
    assert "pallas_call" in str(jaxpr)
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16] == \
        MEASURED_KERNELS[which]


@pytest.mark.parametrize("window", [None, 2048])
def test_windowed_decode_kernel_compiles_at_gqa_widths(monkeypatch, window):
    """The decode kernel as ``trinitym.serve-decode-4k`` runs it (32 query
    heads on 4 KV heads of 128, 96 slots x 64 pages of 64) through Mosaic:
    eight pages a DMA round, the window's first page from the wrapper."""
    on = _described_v5e(monkeypatch)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on)

    b, heads, kvh, dh, ps, pps = 96, 32, 4, 128, 64, 64
    f = kvh * dh
    pool = arg((b * pps, ps, f), jnp.bfloat16)
    try:
        compiled = decode_attention._pallas.lower(
            arg((b, 1, heads, dh), jnp.bfloat16),
            arg((b, 1, f), jnp.bfloat16), arg((b, 1, f), jnp.bfloat16),
            pool, pool, None, None, arg((b, pps), jnp.int32),
            arg((b,), jnp.int32), group=heads // kvh,
            sliding_window=window).compile()
    finally:
        _support.pallas_mode.cache_clear()
    assert "tpu_custom_call" in compiled.as_text()


def test_latent_decode_kernel_compiles_at_published_sizes(monkeypatch):
    """The latent kernel as ``dotsvlm1.serve-decode-8k`` runs it (128
    query heads of 512 + 64 against one shared row a position, 64 slots x
    128 pages of 64, eight pages a DMA round from the ``c`` and the ``kR``
    pool) through Mosaic."""
    on = _described_v5e(monkeypatch)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on)

    b, heads, rank, lanes, ps, pps = 64, 128, 512, 128, 64, 128
    try:
        compiled = decode_attention._latent_pallas.lower(
            arg((b, heads, rank + lanes), jnp.bfloat16),
            arg((b, rank), jnp.bfloat16), arg((b, lanes), jnp.bfloat16),
            arg((b * pps, ps, rank), jnp.bfloat16),
            arg((b * pps, ps, lanes), jnp.bfloat16),
            arg((b, pps), jnp.int32), arg((b,), jnp.int32),
            scale=0.0781).compile()
    finally:
        _support.pallas_mode.cache_clear()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "mla_decode_attention" in text


@pytest.mark.parametrize("form", ["expanded", "absorbed"])
def test_latent_prefill_attention_compiles_at_published_sizes(monkeypatch,
                                                              form):
    """The two flash calls of a latent-attention prefill at
    ``dots-vlm1-inst``'s sizes through Mosaic: a whole prompt of 2,048 at
    q/k head size 192 against v head size 128, and a 2,048-token chunk of
    128 heads over one shared row of 640 / 512 lanes a position, at a
    traced offset into 10,240 cached rows."""
    from apex_tpu.ops.attention import flash_chunk_fwd

    on = _described_v5e(monkeypatch)

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=on)

    if form == "expanded":
        args = (arg(1, 128, 2048, 192), arg(1, 128, 2048, 192),
                arg(1, 128, 2048, 128))
        kw = dict(q_start=0)
    else:
        args = (arg(1, 128, 2048, 640), arg(1, 1, 10240, 640),
                arg(1, 1, 10240, 512))
        kw = dict(block_q=512, block_k=512)
    try:
        if form == "expanded":
            fn = jax.jit(lambda q, k, v: flash_chunk_fwd(
                q, k, v, k_start=0, causal=True, softmax_scale=0.1,
                name="mla_prefill_attention", **kw)[0])
            compiled = fn.lower(*args).compile()
        else:
            fn = jax.jit(lambda q, k, v, s: flash_chunk_fwd(
                q, k, v, q_start=s, k_start=0, causal=True,
                softmax_scale=0.1, name="mla_prefill_attention", **kw)[0])
            compiled = fn.lower(*args, jax.ShapeDtypeStruct(
                (), jnp.int32, sharding=on)).compile()
    finally:
        _support.pallas_mode.cache_clear()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "mla_prefill_attention" in text


@pytest.mark.parametrize("page_size,f,takes", [
    (64, 1024, True), (64, 512, True), (8, 128, True),
    (64, 192, False),      # a rank's slice of GPT-2 124M under tp=4
    (12, 128, False),      # a page that ends inside a tile
])
def test_a_refused_pool_takes_the_reference_and_says_so(
        monkeypatch, caplog, page_size, f, takes):
    """Mosaic slices a pool left in HBM by whole 8 x 128 tiles only: on
    the chip a pool whose pages are not made of them is served by the
    reference, and one ``paged_decode_kernel_refused`` event a shape says
    so (the interpreter takes any shape and says nothing)."""
    pool = jax.ShapeDtypeStruct((4, page_size, f), jnp.bfloat16)
    monkeypatch.setattr(decode_attention, "_REFUSED", set())
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "tpu")
    _support.pallas_mode.cache_clear()
    try:
        with caplog.at_level("WARNING", logger=decode_attention.__name__):
            assert decode_attention._kernel_takes(pool) is takes
            assert decode_attention._kernel_takes(pool) is takes
            said = [r.getMessage() for r in caplog.records
                    if "paged_decode_kernel_refused" in r.getMessage()]
            assert len(said) == (0 if takes else 1)
            assert all(f"minor_dim={f}" in line
                       and f"page_size={page_size}" in line for line in said)
            monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "interpret")
            _support.pallas_mode.cache_clear()
            assert decode_attention._kernel_takes(pool)
            assert len(caplog.records) == len(said)
    finally:
        _support.pallas_mode.cache_clear()


@pytest.mark.parametrize("quantized", [False, True])
def test_decode_kernel_compiles_at_the_smallest_pool_it_takes(monkeypatch,
                                                          quantized):
    """The smallest pool the compiled kernel takes (pages of 8 rows x 128
    lanes), as the interpret-mode parity tests of ``test_serving_paged``
    and ``test_spec_quant`` run it: three pages a DMA round over 8-page
    tables, a verify window, a sliding window, float32 and int8 pools.
    What those tests check interpreted is what Mosaic compiles here."""
    on = _described_v5e(monkeypatch)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on)

    b, w, heads, kvh, dh, ps, pps = 3, 3, 4, 2, 64, 8, 8
    f = kvh * dh
    dtype = jnp.int8 if quantized else jnp.float32
    monkeypatch.setattr(decode_attention, "_BUFFER_BYTES",
                        3 * ps * f * jnp.dtype(dtype).itemsize)
    decode_attention._pallas.clear_cache()
    assert decode_attention._pages_per_round(ps, f, dtype, pps) == 3
    pool = arg((b * pps + 2, ps, f), dtype)
    scales = arg((b * pps + 2, kvh), jnp.float32) if quantized else None
    try:
        compiled = decode_attention._pallas.lower(
            arg((b, w, heads, dh), jnp.float32),
            arg((b, w, f), jnp.float32), arg((b, w, f), jnp.float32),
            pool, pool, scales, scales, arg((b, pps), jnp.int32),
            arg((b,), jnp.int32), group=heads // kvh,
            sliding_window=None if quantized else 20).compile()
    finally:
        decode_attention._pallas.clear_cache()
        _support.pallas_mode.cache_clear()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_kernel_compiles_at_the_gpt2m_cells_shape(monkeypatch):
    """The decode kernel as ``gpt2m.serve-decode`` and ``gpt2m.serve-prefill``
    run it (96 slots, 16 heads of 64, 16 pages of 64 a slot, bf16) through
    Mosaic: four pages a DMA round, two buffers each of K and V."""
    on = _described_v5e(monkeypatch)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on)

    b, heads, dh, ps, pps = 96, 16, 64, 64, 16
    f = heads * dh
    assert decode_attention._pages_per_round(ps, f, jnp.bfloat16, pps) == 4
    pool = arg((b * pps, ps, f), jnp.bfloat16)
    try:
        compiled = decode_attention._pallas.lower(
            arg((b, 1, heads, dh), jnp.bfloat16),
            arg((b, 1, f), jnp.bfloat16), arg((b, 1, f), jnp.bfloat16),
            pool, pool, None, None, arg((b, pps), jnp.int32),
            arg((b,), jnp.int32), group=1, sliding_window=None).compile()
    finally:
        _support.pallas_mode.cache_clear()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("tokens", [96, 4096])
def test_grouped_products_compile_at_published_widths(monkeypatch, tokens):
    """The routed products of a decode step (96 rows x 8, tiles of 16) and
    of the longest prefill (4,096 x 8, tiles of 128) over 128 experts of
    2,048 x 1,024 through Mosaic: two calls, inside the VMEM limit."""
    on = _described_v5e(monkeypatch)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on)

    experts, h, f, k = 128, 2048, 1024, 8
    tile = grouped_matmul.tile_rows_for(tokens * k, experts)
    rows = grouped_matmul.padded_rows(tokens * k, experts, tile)
    assert tile == (16 if tokens == 96 else 128)
    try:
        compiled = grouped_matmul._pallas.lower(
            arg((rows, h), jnp.bfloat16),
            arg((experts, h, 2 * f), jnp.bfloat16),
            arg((experts, f, h), jnp.bfloat16),
            arg((rows // tile,), jnp.int32), arg((), jnp.int32),
            tile_rows=tile).compile()
    finally:
        _support.pallas_mode.cache_clear()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "moe_experts_up" in text and "moe_experts_down" in text
