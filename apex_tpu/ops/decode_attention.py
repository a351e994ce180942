"""Fused decode-step attention over a PAGED KV cache.

The serving engine's decode roofline (PERF.md, BENCH_r05) showed the
gap to the HBM read-bandwidth bound *growing* with batch — 78%/76%/65%
at bs 1/8/32 — which indicts the unfused chain, not the cache reads:
XLA's paged-cache gather materializes a ``[b, S, f]`` temporary (read
pool + write temp + re-read temp = ~3x the stream), and the per-slot
append is a separate scatter program. This module is the fused
alternative (PAPERS.md: "LLM Inference Acceleration via Efficient
Operation Fusion", arXiv 2502.17728; ClusterFusion++'s whole-block
decode fusion is the same territory):

- :func:`fused_paged_decode_attention` — ONE jitted region per decode
  step and layer: the new K/V rows land as a donated in-place scatter,
  and attention is a single VMEM-resident flash pass over the slot's
  mapped pages (Pallas kernel, page table scalar-prefetched so each
  page block DMAs straight from its pool row). The KV stream is read
  from HBM exactly once per step; the only HBM writes are the appended
  rows. No gathered-cache temporary exists in any memory space.

Two extensions raise the effective bandwidth ceiling past the PR 9
roofline (docs/serving.md#kv-quantization, #speculative-decoding):

- **Query windows** (``q`` rank 4): each slot appends and attends over
  ``w`` consecutive rows in one pass — the verify step of
  self-speculative decoding, which amortizes one read of the KV stream
  over up to ``w`` emitted tokens. ``w == 1`` reproduces the PR 9
  single-token step bit-for-bit (the window formulation degenerates to
  the same arrays and the same reduction order).
- **int8 pools with per-(page, kv-head) scales** (``k_scales`` /
  ``v_scales``): pages are the quantization blocks. Appends quantize
  with RESCALE-ON-APPEND — a page's scale only ever grows (scatter-max
  of the incoming rows' absmax), resident int8 rows are rescaled by
  ``old/new``, and the new rows quantize at the final scale — and the
  kernel streams the int8 page as is, folding each page's per-head
  scale into the scores and the weighted values, so the HBM stream is
  half the bf16 bytes with no new read site and no dequantized copy.

Layouts (see docs/serving.md#paged-kv):

- pool: ``[n_pages, page_size, kv_heads * head_dim]`` per layer — the
  fused heads-minor dim keeps every page read full-lane, exactly like
  the flat cache's ``[b, S, h*d]`` form (PERF.md round 5), and is the
  dim :class:`~apex_tpu.serving.fleet.ShardedEngine` shards over the
  tensor axis.
- scale sidecar: ``[n_pages, kv_heads]`` float32 per pool — sharded
  ``P(None, tensor)`` so each rank's scales cover exactly its head
  slice (per-head absmax is rank-local under TP).
- page table: ``[b, pages_per_slot]`` int32, logical page ``j`` of slot
  ``r`` lives in pool row ``page_table[r, j]``; unmapped entries hold
  the out-of-range sentinel ``n_pages`` (reads clamp + mask, scatters
  drop). Window rows past the table's span also clamp to the sentinel,
  so an over-long window can never corrupt the slot's own last page.

Dispatch follows the repo convention (:mod:`apex_tpu.ops._support`):
the Pallas kernel on TPU (or under ``APEX_TPU_FORCE_PALLAS=interpret``
for CI parity), and a pure-``jnp`` reference elsewhere. The reference
reproduces the flat cache's single-token MXU formulation bit-for-bit on
the gathered logical view, so the paged engine stays TOKEN-EXACT
against the flat engine on CPU (the tier-1 parity bar); the kernel's
flash accumulation is validated against the reference to numerical
tolerance in interpret mode, compiled by the real Mosaic compiler in
tier-1 (``tests/test_chip_smoke.py``, no chip needed) and compared on
the chip at GPT-2 124M widths by ``chip_smoke.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.observability.tracing import SCOPE_PAGED_DECODE
from apex_tpu.ops._support import cdiv, pallas_interpret, use_pallas
from apex_tpu.utils.profiling import nvtx_range

__all__ = ["fused_paged_decode_attention", "paged_pages_for",
           "paged_quant_fill", "paged_quant_scatter"]

#: the masked-score floor the flat decode path uses — shared so paged
#: and flat softmax see bitwise-identical masked entries
_NEG = -1e30

#: int8 quantization range: symmetric, -127..127 (keeping -128 out of
#: the code domain makes the scale exactly absmax/127 and negation
#: lossless)
_QMAX = 127.0


def paged_pages_for(tokens: int, page_size: int) -> int:
    """Pages needed to hold ``tokens`` cache rows."""
    return cdiv(tokens, page_size)


def _window_dest(page_table, positions, w, page_size):
    """Scatter coordinates for a ``w``-row append window per slot:
    row ``t`` of slot ``r`` lands at logical position
    ``positions[r] + t``. Positions past the table's span map to the
    sentinel ``n_pages`` (a plain gather would CLAMP to the table's
    last column and corrupt the slot's own final page)."""
    b = page_table.shape[0]
    pps = page_table.shape[1]
    idx = positions[:, None] + jnp.arange(w)[None, :]        # [b, w]
    page_idx = idx // page_size
    dest_page = jnp.take_along_axis(
        page_table, jnp.clip(page_idx, 0, pps - 1), axis=1)
    dest_page = jnp.where(page_idx < pps, dest_page,
                          jnp.int32(2 ** 30))  # past any pool: drops
    return dest_page.astype(jnp.int32), (idx % page_size).astype(jnp.int32)


def _append_rows(pages, rows, page_table, positions, page_size):
    """Scatter each slot's ``w`` new rows at their cache positions.
    One window per slot; with the pool donated into the jitted step this
    compiles to in-place writes, never a pool copy. Unmapped sentinel
    entries (and window rows past the table) drop instead of corrupting
    a foreign page."""
    b, w, f = rows.shape
    dest_page, dest_row = _window_dest(page_table, positions, w, page_size)
    return pages.at[dest_page, dest_row].set(
        rows.astype(pages.dtype), mode="drop")


# -- int8 page quantization --------------------------------------------------


def paged_quant_scatter(pages, scales, rows, dest_page, dest_row):
    """Rescale-on-append row scatter into an int8 pool.

    ``rows`` ``[n, kv_heads * head_dim]`` land at
    ``(dest_page[i], dest_row[i])``; out-of-range ``dest_page`` drops
    the row (sentinel convention). Scale lifecycle: a page's per-kv-head
    scale MONOTONICALLY grows to cover the incoming rows' absmax
    (scatter-max), resident int8 rows of touched pages are rescaled by
    ``old/new`` (duplicate destinations write identical values, so the
    scatter stays deterministic), and the new rows quantize at the
    final scale. A zero scale means "nothing valid resident": the ratio
    rescale then zeroes whatever bits the recycled page held.

    Returns ``(pages, scales)``.
    """
    n_pages, ps, f = pages.shape
    kvh = scales.shape[1]
    dh = f // kvh
    rf = rows.astype(jnp.float32).reshape(-1, kvh, dh)
    want = jnp.max(jnp.abs(rf), axis=-1) / _QMAX             # [n, kvh]
    new_scales = scales.at[dest_page].max(want, mode="drop")
    cf = jnp.clip(dest_page, 0, n_pages - 1)
    ns = new_scales[cf]                                      # [n, kvh]
    safe = jnp.where(ns > 0.0, ns, 1.0)
    ratio = scales[cf] / safe                                # old/new <= 1
    resident = pages[cf].astype(jnp.float32) \
        * jnp.repeat(ratio, dh, axis=-1)[:, None, :]
    pages = pages.at[dest_page].set(
        jnp.clip(jnp.round(resident), -_QMAX, _QMAX).astype(pages.dtype),
        mode="drop")
    q = jnp.clip(jnp.round(rf / safe[:, :, None]), -_QMAX, _QMAX)
    pages = pages.at[dest_page, dest_row].set(
        q.reshape(-1, f).astype(pages.dtype), mode="drop")
    return pages, new_scales


def paged_quant_fill(pages, scales, chunks, dest_page):
    """Whole-page overwrite into an int8 pool (the prefill chunk path):
    ``chunks`` ``[n, page_size, f]`` REPLACE pages ``dest_page`` —
    content and scale alike (``.set``, not ``.max``: a freshly mapped
    page owes nothing to its previous occupant). Sentinel destinations
    drop. Returns ``(pages, scales)``."""
    n, ps, f = chunks.shape
    kvh = scales.shape[1]
    dh = f // kvh
    cf = chunks.astype(jnp.float32).reshape(n, ps, kvh, dh)
    amax = jnp.max(jnp.abs(cf), axis=(1, 3))                 # [n, kvh]
    scale = amax / _QMAX
    safe = jnp.where(scale > 0.0, scale, 1.0)
    q = jnp.clip(jnp.round(cf / safe[:, None, :, None]), -_QMAX, _QMAX)
    pages = pages.at[dest_page].set(
        q.reshape(n, ps, f).astype(pages.dtype), mode="drop")
    scales = scales.at[dest_page].set(scale, mode="drop")
    return pages, scales


def _quant_append(pages, scales, rows, page_table, positions, page_size):
    """Windowed rescale-on-append: the int8 counterpart of
    :func:`_append_rows`."""
    b, w, f = rows.shape
    dest_page, dest_row = _window_dest(page_table, positions, w, page_size)
    return paged_quant_scatter(pages, scales, rows.reshape(b * w, f),
                               dest_page.reshape(-1), dest_row.reshape(-1))


def _dequant_view(pages_g, scales_g, dh, dtype):
    """Gathered int8 pages ``[b, pps, ps, f]`` + gathered scales
    ``[b, pps, kvh]`` -> dequantized ``[b, pps, ps, f]`` in ``dtype``."""
    sc = jnp.repeat(scales_g, dh, axis=-1)[:, :, None, :]    # [b,pps,1,f]
    return (pages_g.astype(jnp.float32) * sc).astype(dtype)


# -- reference path (CPU / pallas off) ---------------------------------------


def _reference(q, k_new, v_new, k_pages, v_pages, k_scales, v_scales,
               page_table, positions, group, sliding_window):
    """Gathered-view reference: append, then run the flat cache's
    single-token MXU formulation (transformer._flat_cache_attention,
    ``s == 1`` branch) over the logical ``[b, S, f]`` view
    ``pool[page_table]``, with the ``w`` window queries folded into the
    query-head axis (every einsum reduction is per-query-column
    independent, so ``w`` windowed queries are bitwise-identical to
    ``w`` sequential single-row calls — and ``w == 1`` is the PR 9
    reference unchanged). Real rows see the exact same operand values
    and reduction order as the flat path (padded rows mask to exact
    zeros), so flat-vs-paged engine parity is bitwise, not approximate."""
    n_pages, page_size, f = k_pages.shape
    b, w, hl, dh = q.shape
    kvh = f // dh
    if k_scales is not None:
        k_pages, k_scales = _quant_append(
            k_pages, k_scales, k_new, page_table, positions, page_size)
        v_pages, v_scales = _quant_append(
            v_pages, v_scales, v_new, page_table, positions, page_size)
    else:
        k_pages = _append_rows(k_pages, k_new, page_table, positions,
                               page_size)
        v_pages = _append_rows(v_pages, v_new, page_table, positions,
                               page_size)
    pt = jnp.minimum(page_table, n_pages - 1)     # clamp sentinels (masked)
    if k_scales is not None:
        ck = _dequant_view(k_pages[pt], k_scales[pt], dh, q.dtype)
        cv = _dequant_view(v_pages[pt], v_scales[pt], dh, q.dtype)
        ck = ck.reshape(b, -1, f)
        cv = cv.reshape(b, -1, f)
    else:
        ck = k_pages[pt].reshape(b, -1, f)
        cv = v_pages[pt].reshape(b, -1, f)
    S = ck.shape[1]
    slots = jnp.arange(S)
    # per-query validity: window query t of slot r covers logical rows
    # [0, positions[r] + t]
    t = (jnp.arange(w * hl) // hl)[None, None, :]
    lim = positions[:, None, None] + t
    invalid = slots[None, :, None] > lim
    if sliding_window is not None:
        invalid = jnp.logical_or(
            invalid, slots[None, :, None] <= lim - sliding_window)
    inv_scale = jnp.sqrt(jnp.asarray(dh, jnp.float32)).astype(q.dtype)
    # K stream through one MXU GEMM per batch (Qblock holds each query
    # head's vector in its K/V head's row block, zeros elsewhere) — the
    # same full-lane formulation as the flat path
    qq = q.reshape(b, w * hl, dh)
    q_tiled = jnp.tile(qq.transpose(0, 2, 1), (1, kvh, 1))
    frow = jnp.arange(kvh * dh)[:, None]
    jcol = jnp.arange(w * hl)[None, :]
    blockmask = (frow // dh == (jcol % hl) // group).astype(q.dtype)
    qblock = q_tiled * blockmask                           # [b, f, w*hl]
    scores = jnp.einsum("bsf,bfh->bsh", ck.astype(q.dtype),
                        qblock) / inv_scale                # [b, S, w*hl]
    sf = jnp.where(invalid, jnp.asarray(_NEG, jnp.float32),
                   scores.astype(jnp.float32))
    sf = sf - jnp.max(sf, axis=1, keepdims=True)
    e = jnp.exp(sf)
    probs = (e / jnp.sum(e, axis=1, keepdims=True)).astype(q.dtype)
    ctx_big = jnp.einsum("bsh,bsf->bhf", probs, cv.astype(q.dtype))
    sel = (jnp.arange(kvh)[None, :]
           == (jnp.arange(hl) // group)[:, None]).astype(q.dtype)
    ctx = jnp.einsum("bwjkd,jk->bwjd",
                     ctx_big.reshape(b, w, hl, kvh, dh), sel)
    return ctx.reshape(b, w, hl * dh), k_pages, v_pages, k_scales, v_scales


# -- Pallas kernel -----------------------------------------------------------


def _decode_kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                   page_size, heads, window, quantized, sliding_window,
                   scale):
    """One (slot, page-block) grid cell of the streaming decode pass.

    The page table is scalar-prefetched, so block ``(r, j)``'s K/V page
    DMAs directly from pool row ``page_table[r, j]`` into VMEM — the
    gather never exists as an array. Softmax is the standard flash
    recurrence over page blocks (running max / normalizer / weighted
    accumulator in VMEM scratch, carried across the slot's inner grid
    iterations); the final block rescales and writes the context rows.

    Everything is 2-D and full-lane — the form Mosaic compiles (an
    in-kernel ``[ps, f] -> [ps, kvh, dh]`` split of the lane dim, 4-D
    transposes and non-leading dot batch dims are all refused at
    ``head_dim`` 64): the ``m = window * heads`` queries arrive as the
    block-masked ``[m, f]`` matrix :func:`_query_block` builds (each
    query's vector in its K/V head's lane block, zeros elsewhere), so
    ``scores = Qblock @ page^T`` is one MXU GEMM over the whole fused
    ``f = kvh * dh`` dim, and ``P @ page`` yields ``[m, f]`` rows whose
    own head's lane block holds that query's context (the caller
    selects it). Quantized pools stream int8 and fold each page's
    per-kv-head scale into the scores / weighted values as a per-query
    column — a query only ever reads its own head's lanes, so scaling
    its row equals dequantizing that head. Pages past the slot's valid
    length are skipped (their DMA is the residual cost of the
    rectangular grid — one page per slot, since consecutive sentinel
    entries clamp to the same block and Pallas does not re-fetch it).
    Under a ``sliding_window`` a page that lies wholly before the window
    of the slot's FIRST query is skipped the same way, and is never
    fetched: the index map (:func:`_page_index`) pins those grid steps
    to the first page in the window."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    r = pl.program_id(0)
    j = pl.program_id(1)
    pos = pos_ref[r]                  # first window row's append index
    m = window * heads

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = j * page_size <= pos + (window - 1)
    if sliding_window is not None:
        # the page's last row is still inside the first query's window
        live = jnp.logical_and(
            live, (j + 1) * page_size > pos - sliding_window + 1)

    @pl.when(live)
    def _accumulate():
        qb = q_ref[0]                                     # [m, f]
        kb = k_ref[0].astype(qb.dtype)                    # [ps, f]
        vb = v_ref[0].astype(qb.dtype)
        s_blk = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [m, ps]
        if quantized:
            # this page's scale per query: column j of the slot's
            # [m, pages_per_slot] table, picked with a lane mask
            lane = jax.lax.broadcasted_iota(jnp.int32, ks_ref.shape[1:], 1)
            k_col = jnp.sum(jnp.where(lane == j, ks_ref[0], 0.0),
                            axis=1, keepdims=True)        # [m, 1]
            v_col = jnp.sum(jnp.where(lane == j, vs_ref[0], 0.0),
                            axis=1, keepdims=True)
            s_blk = s_blk * k_col
        row = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        # window row t of each query (queries are ordered [t, head]);
        # a compare-and-add ladder, no vector integer division
        qi = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
        lim = pos + sum(((qi >= t * heads).astype(jnp.int32)
                         for t in range(1, window)),
                        jnp.zeros((m, 1), jnp.int32))
        invalid = row > lim
        if sliding_window is not None:
            invalid = jnp.logical_or(invalid, row <= lim - sliding_window)
        s_blk = jnp.where(invalid, _NEG, s_blk)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s_blk - m_new)                        # [m, ps]
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [m, f]
        if quantized:
            pv = pv * v_col
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        # l > 0 for every real window row: row `pos + t` itself is valid
        # by construction (garbage rows past the slot's window are
        # normalized over whatever survived the mask — the engine never
        # reads them)
        l = jnp.where(l_ref[...] > 0.0, l_ref[...], 1.0)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _page_index(page_size, sliding_window):
    """The K/V block index map: grid step ``(r, j)`` reads pool row
    ``page_table[r, j]``. Under a window, steps before the first page
    that holds a visible row read THAT page instead (the same block as
    the step that will use it, so the pipeline fetches it once and the
    pages before the window are never read)."""
    if sliding_window is None:
        return lambda r, j, pt, pos: (pt[r, j], 0, 0)

    def index(r, j, pt, pos):
        first = jnp.maximum(pos[r] - sliding_window + 1, 0) // page_size
        return (pt[r, jnp.maximum(j, first)], 0, 0)

    return index


def _query_block(q, kv_head, kvh):
    """``q`` ``[b, w, hl, dh]`` -> the block-masked ``[b, w*hl, kvh*dh]``
    query matrix: each query's vector sits in the lane block of its K/V
    head (``kv_head`` ``[w*hl]``), zeros elsewhere — the row-major twin
    of :func:`_reference`'s ``qblock``."""
    b, w, hl, dh = q.shape
    tiled = jnp.tile(q.reshape(b, w * hl, dh), (1, 1, kvh))
    mask = kv_head[:, None] == (jnp.arange(kvh * dh) // dh)[None, :]
    return jnp.where(mask[None], tiled, jnp.zeros((), q.dtype))


@functools.partial(jax.jit, static_argnames=("group", "sliding_window"))
def _pallas(q, k_new, v_new, k_pages, v_pages, k_scales, v_scales,
            page_table, positions, group, sliding_window):
    n_pages, page_size, f = k_pages.shape
    b, w, hl, dh = q.shape
    kvh = f // dh
    m = w * hl
    pages_per_slot = page_table.shape[1]
    # append first (donated in-place row writes); the kernel then
    # streams pages that already contain the new rows — one read of the
    # stream, w rows written, no ordering hazard (the rows' pages are
    # mapped)
    quantized = k_scales is not None
    if quantized:
        k_pages, k_scales = _quant_append(
            k_pages, k_scales, k_new, page_table, positions, page_size)
        v_pages, v_scales = _quant_append(
            v_pages, v_scales, v_new, page_table, positions, page_size)
    else:
        k_pages = _append_rows(k_pages, k_new, page_table, positions,
                               page_size)
        v_pages = _append_rows(v_pages, v_new, page_table, positions,
                               page_size)
    pt = jnp.minimum(page_table, n_pages - 1).astype(jnp.int32)
    # K/V head of each of the m queries (ordered [window row, head])
    kv_head = (jnp.arange(m) % hl) // group

    kernel = functools.partial(
        _decode_kernel, page_size=page_size, heads=hl, window=w,
        quantized=quantized, sliding_window=sliding_window,
        scale=1.0 / float(dh) ** 0.5)
    page = _page_index(page_size, sliding_window)
    in_specs = [
        pl.BlockSpec((1, m, f), lambda r, j, pt, pos: (r, 0, 0)),
        pl.BlockSpec((1, page_size, f), page),
        pl.BlockSpec((1, page_size, f), page),
    ]
    inputs = [pt, positions.astype(jnp.int32),
              _query_block(q, kv_head, kvh), k_pages, v_pages]
    if quantized:
        # per-(slot, query, page) scales: the page's sidecar row gathered
        # to the table layout and expanded to each query's own K/V head
        # — a [m, pages_per_slot] f32 tile per slot, resident across the
        # slot's page loop next to the int8 stream
        def per_query(scales):
            return scales[pt][:, :, kv_head].transpose(0, 2, 1)

        spec = pl.BlockSpec((1, m, pages_per_slot),
                            lambda r, j, pt, pos: (r, 0, 0))
        in_specs += [spec, spec]
        inputs += [per_query(k_scales), per_query(v_scales)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pages_per_slot),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, m, f), lambda r, j, pt, pos: (r, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((m, 1), jnp.float32),      # running max
            pltpu.VMEM((m, 1), jnp.float32),      # normalizer
            pltpu.VMEM((m, f), jnp.float32),      # weighted accumulator
        ])
    ctx_big = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, m, f), q.dtype),
        interpret=pallas_interpret(),
        name="paged_decode_attention",
    )(*inputs)
    # each query keeps its own K/V head's lane block
    sel = (jnp.arange(kvh)[None, :]
           == (jnp.arange(hl) // group)[:, None]).astype(q.dtype)
    ctx = jnp.einsum("bwjkd,jk->bwjd",
                     ctx_big.reshape(b, w, hl, kvh, dh), sel)
    return ctx.reshape(b, w, hl * dh), k_pages, v_pages, k_scales, v_scales


def fused_paged_decode_attention(q, k_new, v_new, k_pages, v_pages,
                                 page_table, positions, *,
                                 queries_per_group: int = 1,
                                 sliding_window=None,
                                 k_scales=None, v_scales=None):
    """One fused decode step for one layer over the paged KV pool.

    Args:
      q: ``[b, local_heads, head_dim]`` (single-token decode) or
        ``[b, w, local_heads, head_dim]`` (a ``w``-row verify window —
        speculative decoding) — query vectors, rope already applied.
      k_new, v_new: ``[b, kv_heads * head_dim]`` (or
        ``[b, w, kv_heads * head_dim]``) — this step's K/V rows.
      k_pages, v_pages: ``[n_pages, page_size, kv_heads * head_dim]`` —
        the layer's page pool (bf16/f32, or int8 with scales).
      page_table: ``[b, pages_per_slot]`` int32 — pool rows backing each
        slot's logical pages; unmapped entries hold the sentinel
        ``n_pages``.
      positions: ``[b]`` int32 — each slot's append index (tokens
        already cached). Window row ``t`` lands at ``positions[r] + t``
        — its page MUST be mapped for rows the engine will read back
        (rows past the table clamp to the sentinel and drop) — and
        window query ``t`` attends over logical rows
        ``[0, positions[r] + t]``.
      queries_per_group: query heads per K/V head (GQA/MQA grouping).
      sliding_window: optional Mistral-style local-attention window.
      k_scales, v_scales: ``[n_pages, kv_heads]`` float32 per-page
        scale sidecars — REQUIRED with int8 pools, forbidden otherwise.

    Returns ``(ctx, k_pages, v_pages)`` — plus ``k_scales, v_scales``
    when quantized. ``ctx`` is ``[b, local_heads * head_dim]`` for
    rank-3 ``q``, else ``[b, w, local_heads * head_dim]``.
    """
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
        k_new = k_new[:, None]
        v_new = v_new[:, None]
    if q.ndim != 4:
        raise ValueError(
            f"q must be [b, heads, head_dim] or [b, w, heads, head_dim], "
            f"got {q.shape}")
    if k_pages.ndim != 3 or k_pages.shape != v_pages.shape:
        raise ValueError(
            f"pools must be matching [n_pages, page_size, kv_heads * "
            f"head_dim], got {k_pages.shape} / {v_pages.shape}")
    b, w, hl, dh = q.shape
    if hl % queries_per_group:
        raise ValueError(
            f"heads ({hl}) not divisible by queries_per_group "
            f"({queries_per_group})")
    kvh = hl // queries_per_group
    if k_pages.shape[-1] != kvh * dh:
        raise ValueError(
            f"pool minor dim {k_pages.shape[-1]} != kv_heads * head_dim "
            f"({kvh} * {dh})")
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales, or neither")
    if (k_pages.dtype == jnp.int8) != (k_scales is not None):
        raise ValueError(
            f"int8 pools need scale sidecars (and only int8 pools take "
            f"them); pool dtype {k_pages.dtype}, "
            f"scales {'set' if k_scales is not None else 'None'}")
    if k_scales is not None and k_scales.shape != (k_pages.shape[0], kvh):
        raise ValueError(
            f"scales must be [n_pages, kv_heads] = "
            f"({k_pages.shape[0]}, {kvh}), got {k_scales.shape}")
    fn = _pallas if use_pallas() else _reference
    with nvtx_range(SCOPE_PAGED_DECODE):
        ctx, k_pages, v_pages, k_scales, v_scales = fn(
            q, k_new, v_new, k_pages, v_pages, k_scales, v_scales,
            page_table, positions, queries_per_group, sliding_window)
    if squeeze:
        ctx = ctx[:, 0]
    if k_scales is None:
        return ctx, k_pages, v_pages
    return ctx, k_pages, v_pages, k_scales, v_scales
