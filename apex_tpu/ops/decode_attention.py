"""Fused decode-step attention over a PAGED KV cache.

The serving engine's decode roofline (a capture from before PR 1, on
another jax) showed the gap to the HBM read-bandwidth bound *growing* with batch — 78%/76%/65%
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
  live pages (Pallas kernel: one grid step a slot, the page table and
  each slot's page range scalar-prefetched, the pools left in HBM and
  the slot's pages copied by hand, several a DMA round, straight from
  their pool rows). The KV stream is read from HBM exactly once per
  step; the only HBM writes are the appended rows. No gathered-cache
  temporary exists in any memory space.

The walk over a slot's pages is a loop INSIDE the kernel, not a grid
axis (PR 31; PERF.md section 6): its trip count is the slot's own
``[first, stop)`` page range (:func:`paged_page_range`: from its
position, the verify window and the layer's sliding window), so a page
that holds no visible row — past the position, before the window, or
of a slot that holds no request — costs nothing, not a grid step. A
round brings ``_BUFFER_BYTES`` of pages (4 at GPT-2 medium's 128 KB
page, 8 at Trinity-Mini's 64 KB, twice that from int8 pools) into one
of two buffers while the flash recurrence runs once over the other;
the shapes, and so the one compiled decode program, do not depend on
any of it.

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

A second kernel serves LATENT attention (:func:`fused_latent_decode_
attention`, the end of this file): one shared row a token in a ``c`` and
a ``kR`` pool, read by all query heads in the absorbed form, with the same
page walk.

Dispatch follows the repo convention (:mod:`apex_tpu.ops._support`):
the Pallas kernel on TPU (or under ``APEX_TPU_FORCE_PALLAS=interpret``
for CI parity), and a pure-``jnp`` reference elsewhere. The reference
reproduces the flat cache's single-token MXU formulation bit-for-bit on
the gathered logical view, so the engine stays TOKEN-EXACT against
per-request decode on the flat cache on CPU (the tier-1 parity bar:
``tests/serving_reference.py``); the kernel's
flash accumulation is validated against the reference to numerical
tolerance in interpret mode, compiled by the real Mosaic compiler in
tier-1 (``tests/test_chip_smoke.py``, no chip needed) and compared on
the chip at GPT-2 124M widths by ``chip_smoke.py``. A pool whose pages
are not whole ``8 x 128`` tiles takes the reference on the chip too,
and one ``paged_decode_kernel_refused`` event says so
(:func:`_kernel_takes`).
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.observability.tracing import (SCOPE_MLA_DECODE,
                                            SCOPE_PAGED_DECODE)
from apex_tpu.ops._support import (cdiv, pallas_interpret, round_up,
                                   use_pallas)
from apex_tpu.utils.logging import log_event
from apex_tpu.utils.profiling import nvtx_range

__all__ = ["fused_paged_decode_attention", "fused_latent_decode_attention",
           "latent_rope_lanes", "paged_page_range", "paged_pages_for",
           "paged_quant_fill", "paged_quant_scatter"]

#: the masked-score floor the flat decode path uses — shared so paged
#: and flat softmax see bitwise-identical masked entries
_NEG = -1e30

#: bytes of one VMEM buffer of K (or V) pages in the decode kernel: a DMA
#: round brings as many pages as fit (PERF.md section 6, PR 31: the probe)
_BUFFER_BYTES = 512 * 1024

_LOG = logging.getLogger(__name__)

#: pool shapes ``(page_size, minor dim)`` already reported as refused by
#: the compiled kernel (:func:`_kernel_takes`)
_REFUSED: set = set()

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
    zeros), so the engine's parity with decode on the flat cache is
    bitwise, not approximate."""
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


def paged_page_range(positions, w, page_size, sliding_window=None):
    """The logical pages ``[first, stop)`` of each slot that hold a row
    some query of its ``w``-row window may see: ``stop`` is one past the
    page of the last window row ``positions + w - 1``; ``first`` is the
    page of the oldest row inside the FIRST query's ``sliding_window``
    (later window rows only see later rows), 0 without one. Pure
    arithmetic on whatever array kind ``positions`` is (``numpy`` on the
    host, ``jnp`` inside a program): the kernel's page walk, the engine's
    ``pages`` span attribute and the tests all read the range here."""
    stop = (positions + (w - 1)) // page_size + 1
    if sliding_window is None:
        return positions * 0, stop
    return (positions - sliding_window + 1).clip(0) // page_size, stop


def _pages_per_round(page_size, f, dtype, pages_per_slot):
    """How many pages one DMA round brings: as many as fit one VMEM
    buffer of :data:`_BUFFER_BYTES`, at least one, at most the table's
    width (``pages_per_slot`` need not divide by it)."""
    page_bytes = page_size * f * jnp.dtype(dtype).itemsize
    return max(1, min(pages_per_slot, _BUFFER_BYTES // page_bytes))


def _kernel_takes(pages) -> bool:
    """Whether the kernel can be compiled for this pool. Mosaic (libtpu
    0.0.34) slices a pool left in HBM only by whole ``8 x 128`` tiles,
    the page's own two dims included, so a page has to be a whole number
    of them: ``page_size`` a multiple of 8 and the fused minor dim a
    multiple of 128 (every benchmarked width; measured chip-free). The
    interpreter takes any shape. On a chip any other pool is served by
    the reference, and SAYS so, once a shape: its gather reads the
    stream about three times (a rank's slice of GPT-2 124M under
    ``tp=4`` is 192 lanes wide, for one)."""
    _, page_size, f = pages.shape
    if pallas_interpret() or (page_size % 8 == 0 and f % 128 == 0):
        return True
    if (page_size, f) not in _REFUSED:
        _REFUSED.add((page_size, f))
        log_event(_LOG, "paged_decode_kernel_refused", page_size=page_size,
                  minor_dim=f, served_by="reference",
                  why="a page is not whole 8 x 128 tiles")
    return False


class _PageWalk:
    """The double-buffered walk over a slot's live pages that the decode
    kernels share (one grid step a slot, the grid axis sequential).

    ``pools`` pairs each pool left in HBM with its ``[2, pages_per_round *
    page_size, lanes]`` VMEM buffer. A round's pages are copied by hand
    (``make_async_copy`` from pool row ``page_table[slot, j]``) into one
    of the two buffers of each pool, and every round starts the NEXT
    round's copies into the other buffer before the kernel waits on its
    own: the next round of this slot or, on a slot's last round, the
    first round of the next slot that has one (``head_ref[r]`` is the
    first slot ``>= r`` with a page to read, ``b`` if none), so the
    copies of ``pages_per_round`` pages are always in flight and a slot's
    first page never waits on an idle pipeline. Which buffer the next
    slot starts in is carried across grid steps in ``parity_ref`` (SMEM).
    A page outside ``[first, stop)`` is not copied; its buffer rows keep
    an earlier round's page, maybe ANOTHER slot's, and a kernel that
    multiplies weights by them zeroes them first (:meth:`wait_or_zero`:
    a masked row's weight is 0, but ``0 x NaN`` is NaN on the MXU).
    ``first_ref`` None: every slot's walk starts at its page 0."""

    def __init__(self, pt_ref, first_ref, stop_ref, head_ref, pools, sems,
                 parity_ref, *, page_size, pages_per_round):
        self.pt_ref, self.first_ref = pt_ref, first_ref
        self.stop_ref, self.head_ref = stop_ref, head_ref
        self.pools, self.sems = pools, sems
        self.parity_ref = parity_ref
        self.page_size, self.pages_per_round = page_size, pages_per_round
        self.r = pl.program_id(0)

    def round_copies(self, slot, rnd, buf):
        """``(whether it is made, [a copy a pool])`` for each page of round
        ``rnd`` of ``slot`` into buffer ``buf``: built alike to start a
        round and to wait on it."""
        pages_per_slot = self.pt_ref.shape[1]
        if self.first_ref is not None:
            base = self.first_ref[slot] + rnd * self.pages_per_round
        copies = []
        for c in range(self.pages_per_round):
            # (the index arithmetic each kernel was measured with, op for
            # op: the walk traces to the kernels of PR 31 and PR 34)
            j = (rnd * self.pages_per_round + c if self.first_ref is None
                 else base + c)
            page = self.pt_ref[slot, jnp.minimum(j, pages_per_slot - 1)]
            rows = pl.ds(c * self.page_size, self.page_size)
            copies.append((
                j < self.stop_ref[slot],
                [pltpu.make_async_copy(hbm.at[page], vmem.at[buf, rows],
                                       self.sems.at[ix, buf])
                 for ix, (hbm, vmem) in enumerate(self.pools)]))
        return copies

    def start_round(self, slot, rnd, buf):
        """Start round ``rnd`` of ``slot`` (nothing if ``slot == b``)."""
        b = self.pt_ref.shape[0]
        for made, copies in self.round_copies(
                jnp.minimum(slot, b - 1), rnd, buf):
            @pl.when(jnp.logical_and(slot < b, made))
            def _start():
                for copy in copies:
                    copy.start()

    def start_call(self):
        """On the call's first grid step: its first round."""
        @pl.when(self.r == 0)
        def _first_round_of_the_call():
            self.parity_ref[0] = 0
            self.start_round(self.head_ref[0], 0, 0)

    def open(self):
        """This slot's ``(first page, stop page, rounds)``."""
        if self.first_ref is None:
            first, stop = 0, self.stop_ref[self.r]
            pages = stop
        else:
            first, stop = self.first_ref[self.r], self.stop_ref[self.r]
            pages = stop - first
        self.rounds = jax.lax.div(pages + (self.pages_per_round - 1),
                                  self.pages_per_round)
        self.parity = self.parity_ref[0]
        return first, stop, self.rounds

    def run(self, one_round):
        """``one_round(i, buf, copies)`` for each round of this slot, the
        round after it started first."""
        r, rounds, parity = self.r, self.rounds, self.parity

        def body(i, carry):
            buf = jax.lax.rem(parity + i, 2)
            last = i + 1 == rounds
            self.start_round(jnp.where(last, self.head_ref[r + 1], r),
                             jnp.where(last, 0, i + 1), 1 - buf)
            one_round(i, buf, self.round_copies(r, i, buf))
            return carry

        jax.lax.fori_loop(0, rounds, body, 0)
        self.parity_ref[0] = jax.lax.rem(parity + rounds, 2)

    @staticmethod
    def wait(copies, *pools):
        """Wait on the copies of a round into the buffers of ``pools``."""
        for made, of_pool in copies:
            @pl.when(made)
            def _arrived():
                for pool in pools:
                    of_pool[pool].wait()

    def wait_or_zero(self, copies, buf, *pools):
        """:meth:`wait`, and zero the buffer rows of the pages that were
        not copied."""
        for c, (made, of_pool) in enumerate(copies):
            @pl.when(made)
            def _arrived():
                for pool in pools:
                    of_pool[pool].wait()

            @pl.when(jnp.logical_not(made))
            def _no_stale_rows():
                rows = pl.ds(c * self.page_size, self.page_size)
                for pool in pools:
                    vmem = self.pools[pool][1]
                    vmem[buf, rows] = jnp.zeros(
                        (self.page_size, vmem.shape[2]), vmem.dtype)


def _decode_kernel(pt_ref, pos_ref, first_ref, stop_ref, head_ref, q_ref,
                   k_hbm, v_hbm, *rest, page_size, heads, window, quantized,
                   sliding_window, scale, pages_per_round):
    """One slot of the streaming decode pass: grid step ``r`` walks slot
    ``r``'s live pages ``first_ref[r] .. stop_ref[r]`` in an in-kernel
    loop whose trip count is data, ``pages_per_round`` pages a round.

    Both pools stay in HBM; the page table, the positions, each slot's
    page range (:func:`paged_page_range`; an idle slot's is empty) and
    ``head_ref`` are scalar-prefetched, and :class:`_PageWalk` copies the
    pages. The gather never exists as an array, a page outside ``[first,
    stop)`` is neither copied nor multiplied, and a slot with no page (an
    idle one) costs one empty grid step and writes zeros.

    Softmax is the standard flash recurrence over rounds (running max /
    normalizer / weighted accumulator in VMEM scratch), once per
    ``[pages_per_round * page_size, f]`` block. A round's pages past
    ``stop`` are not copied, and their buffer rows keep an earlier
    round's page — maybe ANOTHER slot's. Every such row lies past the
    last window row, so the mask removes its score whatever the K rows
    hold; its weight is 0, but ``0 x NaN`` is NaN on the MXU, so a
    partial round zeroes those rows of its V buffer (and a quantized one
    reads 0 for their scales) before ``P @ V``: a slot reads nothing but
    its own pages, and one slot's non-finite rows cannot reach another.

    Everything is 2-D and full-lane — the form Mosaic compiles (an
    in-kernel ``[ps, f] -> [ps, kvh, dh]`` split of the lane dim, 4-D
    transposes and non-leading dot batch dims are all refused at
    ``head_dim`` 64): the ``m = window * heads`` queries arrive as the
    block-masked ``[m, f]`` matrix :func:`_query_block` builds (each
    query's vector in its K/V head's lane block, zeros elsewhere), so
    ``scores = Qblock @ block^T`` is one MXU GEMM over the whole fused
    ``f = kvh * dh`` dim, and ``P @ block`` yields ``[m, f]`` rows whose
    own head's lane block holds that query's context (the caller
    selects it). Quantized pools stream int8 and fold each page's
    per-kv-head scale into the scores / the weights as a per-query value
    spread over that page's columns — a query only ever reads its own
    head's lanes, so scaling its row equals dequantizing that head."""
    if quantized:
        ks_ref, vs_ref, o_ref, *scratch = rest
    else:
        o_ref, *scratch = rest
    kbuf, vbuf, sems, parity_ref, m_ref, l_ref, acc_ref = scratch
    walk = _PageWalk(pt_ref, first_ref, stop_ref, head_ref,
                     [(k_hbm, kbuf), (v_hbm, vbuf)], sems, parity_ref,
                     page_size=page_size, pages_per_round=pages_per_round)
    m = window * heads
    span = pages_per_round * page_size         # rows of one buffer
    walk.start_call()
    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    pos = pos_ref[walk.r]             # first window row's append index
    first, stop, _ = walk.open()
    col = jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
    # the last row each query sees: window row t of each query (queries
    # are ordered [t, head]; a compare-and-add ladder, no vector integer
    # division), never past the table (a window row there sees the
    # table's rows, not what a round's uncopied pages left in the buffer)
    qi = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    lim = pos + sum(((qi >= t * heads).astype(jnp.int32)
                     for t in range(1, window)),
                    jnp.zeros((m, 1), jnp.int32))
    newest = jnp.minimum(lim, stop * page_size - 1)

    def one_round(i, buf, copies):
        base = first + i * pages_per_round
        walk.wait(copies, 0)
        qb = q_ref[0]                                     # [m, f]
        kb = kbuf[buf].astype(qb.dtype)                   # [span, f]
        s_blk = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [m, span]
        if quantized:
            def per_column(s_ref):
                # each page's scale per query (column `base + c` of the
                # slot's [m, pages_per_slot] table, picked with a lane
                # mask; 0 past `stop`, where the table names no page of
                # this slot) spread over that page's columns of the block
                lane = jax.lax.broadcasted_iota(
                    jnp.int32, s_ref.shape[1:], 1)
                out = jnp.zeros((m, span), jnp.float32)
                for c in range(pages_per_round):
                    pick = jnp.sum(
                        jnp.where(jnp.logical_and(lane == base + c,
                                                  lane < stop),
                                  s_ref[0], 0.0),
                        axis=1, keepdims=True)            # [m, 1]
                    mine = jnp.logical_and(col >= c * page_size,
                                           col < (c + 1) * page_size)
                    out = jnp.where(mine, pick, out)
                return out

            s_blk = s_blk * per_column(ks_ref)
        row = base * page_size + col
        invalid = row > newest
        if sliding_window is not None:
            invalid = jnp.logical_or(invalid, row <= lim - sliding_window)
        s_blk = jnp.where(invalid, _NEG, s_blk)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s_blk - m_new)                        # [m, span]
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        if quantized:
            p = p * per_column(vs_ref)
        walk.wait_or_zero(copies, buf, 1)
        vb = vbuf[buf].astype(qb.dtype)
        pv = jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [m, f]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    walk.run(one_round)
    # l > 0 for every real window row: row `pos + t` itself is valid by
    # construction (garbage rows past the slot's window are normalized
    # over whatever survived the mask — the engine never reads them); a
    # slot with no page keeps l == 0, acc == 0 and writes zeros
    l = jnp.where(l_ref[...] > 0.0, l_ref[...], 1.0)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


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
    positions = positions.astype(jnp.int32)
    # the pages the kernel walks: none for a slot that holds no request
    # (the engine leaves its table row at the sentinel), never past the
    # table (an over-long window's rows there were dropped by the append)
    first, stop = paged_page_range(positions, w, page_size, sliding_window)
    stop = jnp.where(page_table[:, 0] >= n_pages, 0,
                     jnp.minimum(stop, pages_per_slot)).astype(jnp.int32)
    first = jnp.minimum(first, stop).astype(jnp.int32)
    # head[r]: the first slot >= r with a page to read (b: none) — where
    # the kernel's prefetch goes when slot r - 1 runs out of rounds
    slot_ix = jnp.arange(b + 1, dtype=jnp.int32)
    head = jax.lax.cummin(
        jnp.where(jnp.append(stop > first, True), slot_ix, b),
        reverse=True)
    # K/V head of each of the m queries (ordered [window row, head])
    kv_head = (jnp.arange(m) % hl) // group

    pages_per_round = _pages_per_round(page_size, f, k_pages.dtype,
                                       pages_per_slot)
    kernel = functools.partial(
        _decode_kernel, page_size=page_size, heads=hl, window=w,
        quantized=quantized, sliding_window=sliding_window,
        scale=1.0 / float(dh) ** 0.5, pages_per_round=pages_per_round)
    per_slot = pl.BlockSpec((1, m, f), lambda r, *_: (r, 0, 0))
    in_specs = [per_slot,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    inputs = [pt, positions, first, stop, head,
              _query_block(q, kv_head, kvh), k_pages, v_pages]
    if quantized:
        # per-(slot, query, page) scales: the page's sidecar row gathered
        # to the table layout and expanded to each query's own K/V head
        # — a [m, pages_per_slot] f32 tile per slot, resident across the
        # slot's page loop next to the int8 stream
        def per_query(scales):
            return scales[pt][:, :, kv_head].transpose(0, 2, 1)

        spec = pl.BlockSpec((1, m, pages_per_slot), lambda r, *_: (r, 0, 0))
        in_specs += [spec, spec]
        inputs += [per_query(k_scales), per_query(v_scales)]
    buffers = pltpu.VMEM((2, pages_per_round * page_size, f), k_pages.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b,),
        in_specs=in_specs,
        out_specs=per_slot,
        scratch_shapes=[
            buffers,                              # K rounds, two deep
            buffers,                              # V rounds, two deep
            pltpu.SemaphoreType.DMA((2, 2)),      # [K / V, buffer]
            pltpu.SMEM((1,), jnp.int32),          # the next round's buffer
            pltpu.VMEM((m, 1), jnp.float32),      # running max
            pltpu.VMEM((m, 1), jnp.float32),      # normalizer
            pltpu.VMEM((m, f), jnp.float32),      # weighted accumulator
        ])
    ctx_big = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, m, f), q.dtype),
        # the buffer parity and the prefetch run from one slot to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
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
    fn = _pallas if use_pallas() and _kernel_takes(k_pages) else _reference
    with nvtx_range(SCOPE_PAGED_DECODE):
        ctx, k_pages, v_pages, k_scales, v_scales = fn(
            q, k_new, v_new, k_pages, v_pages, k_scales, v_scales,
            page_table, positions, queries_per_group, sliding_window)
    if squeeze:
        ctx = ctx[:, 0]
    if k_scales is None:
        return ctx, k_pages, v_pages
    return ctx, k_pages, v_pages, k_scales, v_scales


# -- the latent (MLA) pool: one shared row a token, read by every head --------
#
# A latent-attention layer (models/transformer.py ``LatentAttention``)
# caches ``c`` (``kv_lora_rank`` values, normed) and ``kR`` (the shared
# rotary key, rotated) a token: no head axis. They live in TWO pools paged
# by the one table, ``c`` ``[n_pages, page_size, rank]`` and ``kR``
# ``[n_pages, page_size, latent_rope_lanes(rope)]`` (zero lanes behind the
# rotary part: 576 values are 4.5 lane tiles, and Mosaic copies a page out
# of a pool in HBM by whole tiles only), so the pair moves through the
# engine like a K and a V pool (docs/serving.md#latent-kv). The decode
# step is the ABSORBED form: the caller folds ``W_UK`` into the query
# (``qL``) and ``W_UV`` into the result, so the kernel multiplies all
# ``heads`` queries of width ``rank + rope`` against the one row, and the
# values are the ``c`` half of that same row.


def latent_rope_lanes(rope_dim: int) -> int:
    """Minor dim of the ``kR`` pool: the rotary part padded to whole
    128-lane tiles."""
    return round_up(rope_dim, 128)


def _latent_reference(q, c_new, kr_new, c_pages, kr_pages, page_table,
                      positions, scale):
    """Append, then softmax over the gathered logical view
    ``pool[page_table]`` in float32: ``score = q . [c | kR]``, values the
    ``c`` half. A slot whose table maps no page (an idle one) reads
    zeros, as the kernel writes them."""
    n_pages, page_size, rank = c_pages.shape
    b = q.shape[0]
    c_pages = _append_rows(c_pages, c_new[:, None], page_table, positions,
                           page_size)
    kr_pages = _append_rows(kr_pages, kr_new[:, None], page_table,
                            positions, page_size)
    pt = jnp.minimum(page_table, n_pages - 1)
    c = c_pages[pt].reshape(b, -1, rank).astype(jnp.float32)
    kr = kr_pages[pt].reshape(b, c.shape[1], -1).astype(jnp.float32)
    qf = q.astype(jnp.float32)
    scores = (jnp.einsum("bhr,bsr->bhs", qf[..., :rank], c)
              + jnp.einsum("bhe,bse->bhs", qf[..., rank:], kr)) * scale
    seen = jnp.arange(c.shape[1])[None, None, :] <= positions[:, None, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, _NEG), axis=-1)
    # a masked row may hold another tenant's non-finite values: 0 x NaN
    out = jnp.einsum("bhs,bsr->bhr", probs,
                     jnp.where(seen.transpose(0, 2, 1), c, 0.0))
    live = (page_table[:, 0] < n_pages)[:, None, None]
    return (jnp.where(live, out, 0.0).astype(q.dtype), c_pages, kr_pages)


def _latent_kernel(pt_ref, pos_ref, stop_ref, head_ref, q_ref, c_hbm, kr_hbm,
                   o_ref, cbuf, krbuf, sems, parity_ref, m_ref, l_ref,
                   acc_ref, *, page_size, rank, scale, pages_per_round):
    """One slot of the absorbed latent decode pass: :class:`_PageWalk`
    over the ``c`` and ``kR`` pools (one grid step a slot, an in-kernel
    loop over the slot's live pages ``0 .. stop_ref[r]``). A round's rows
    are scored once for all ``heads`` queries, ``qL @ c^T + qR @ kR^T``
    (two MXU products over 512 and 128 lanes), and the same ``c`` buffer
    is the values, so both buffers' rows of pages past ``stop`` are
    zeroed before use."""
    walk = _PageWalk(pt_ref, None, stop_ref, head_ref,
                     [(c_hbm, cbuf), (kr_hbm, krbuf)], sems, parity_ref,
                     page_size=page_size, pages_per_round=pages_per_round)
    span = pages_per_round * page_size
    walk.start_call()
    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    newest = pos_ref[walk.r]
    walk.open()
    col = jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)

    def one_round(i, buf, copies):
        walk.wait_or_zero(copies, buf, 0, 1)
        qb = q_ref[0]                                     # [heads, r + e]
        cb = cbuf[buf].astype(qb.dtype)                   # [span, rank]
        kb = krbuf[buf].astype(qb.dtype)                  # [span, e]
        contract = (((1,), (1,)), ((), ()))
        s_blk = (jax.lax.dot_general(
            qb[:, :rank], cb, contract, preferred_element_type=jnp.float32)
            + jax.lax.dot_general(
                qb[:, rank:], kb, contract,
                preferred_element_type=jnp.float32)) * scale
        row = i * span + col
        s_blk = jnp.where(row > newest, _NEG, s_blk)      # [heads, span]
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s_blk - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(cb.dtype), cb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [heads, rank]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    walk.run(one_round)
    l = jnp.where(l_ref[...] > 0.0, l_ref[...], 1.0)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale",))
def _latent_pallas(q, c_new, kr_new, c_pages, kr_pages, page_table,
                   positions, scale):
    n_pages, page_size, rank = c_pages.shape
    lanes = kr_pages.shape[2]
    b, heads, width = q.shape
    pages_per_slot = page_table.shape[1]
    c_pages = _append_rows(c_pages, c_new[:, None], page_table, positions,
                           page_size)
    kr_pages = _append_rows(kr_pages, kr_new[:, None], page_table,
                            positions, page_size)
    pt = jnp.minimum(page_table, n_pages - 1).astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    _, stop = paged_page_range(positions, 1, page_size)
    stop = jnp.where(page_table[:, 0] >= n_pages, 0,
                     jnp.minimum(stop, pages_per_slot)).astype(jnp.int32)
    slot_ix = jnp.arange(b + 1, dtype=jnp.int32)
    head = jax.lax.cummin(
        jnp.where(jnp.append(stop > 0, True), slot_ix, b), reverse=True)
    pages_per_round = _pages_per_round(page_size, rank, c_pages.dtype,
                                       pages_per_slot)
    kernel = functools.partial(
        _latent_kernel, page_size=page_size, rank=rank, scale=scale,
        pages_per_round=pages_per_round)
    rows = pages_per_round * page_size
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, heads, width), lambda r, *_: (r, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, heads, rank), lambda r, *_: (r, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, rows, rank), c_pages.dtype),    # c rounds
            pltpu.VMEM((2, rows, lanes), kr_pages.dtype),  # kR rounds
            pltpu.SemaphoreType.DMA((2, 2)),      # [c / kR, buffer]
            pltpu.SMEM((1,), jnp.int32),          # the next round's buffer
            pltpu.VMEM((heads, 1), jnp.float32),  # running max
            pltpu.VMEM((heads, 1), jnp.float32),  # normalizer
            pltpu.VMEM((heads, rank), jnp.float32),   # weighted accumulator
        ])
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, heads, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(),
        name=SCOPE_MLA_DECODE,
    )(pt, positions, stop, head, q, c_pages, kr_pages)
    return out, c_pages, kr_pages


def fused_latent_decode_attention(q_latent, q_rope, c_new, kr_new, c_pages,
                                  kr_pages, page_table, positions, *,
                                  softmax_scale: float):
    """One absorbed decode step of one latent-attention layer.

    Args:
      q_latent: ``[b, heads, rank]``, each head's content query times its
        ``W_UK`` (the query in the latent's coordinates).
      q_rope: ``[b, heads, rope]``, the rotary part, rotated.
      c_new: ``[b, rank]``, this step's latent row (normed); ``kr_new``
        ``[b, rope]``, its shared rotary key (rotated).
      c_pages, kr_pages: ``[n_pages, page_size, rank]`` and ``[n_pages,
        page_size, latent_rope_lanes(rope)]``, the layer's two pools.
      page_table, positions: as :func:`fused_paged_decode_attention`.
      softmax_scale: times the scores (the model's, YaRN's share in it).

    Returns ``(o_latent [b, heads, rank], c_pages, kr_pages)``:
    ``softmax(qL . c_j + qR . kR_j) @ c`` over rows ``0..positions[r]``,
    the new row appended first. The caller multiplies by ``W_UV``.
    """
    rank, lanes = c_pages.shape[2], kr_pages.shape[2]
    rope = q_rope.shape[-1]
    if (q_latent.shape[-1] != rank or c_new.shape[-1] != rank
            or lanes != latent_rope_lanes(rope)
            or c_pages.shape[:2] != kr_pages.shape[:2]):
        raise ValueError(
            f"latent pools must be [n_pages, page_size, rank] and "
            f"[n_pages, page_size, {latent_rope_lanes(rope)}] for queries "
            f"of {q_latent.shape[-1]} + {rope}; got {c_pages.shape} / "
            f"{kr_pages.shape}")
    pad = [(0, 0)] * (q_rope.ndim - 1) + [(0, lanes - rope)]
    q = jnp.concatenate([q_latent, jnp.pad(q_rope, pad)], axis=-1)
    kr_new = jnp.pad(kr_new, ((0, 0), (0, lanes - rope)))
    fn = (_latent_pallas if use_pallas() and _kernel_takes(c_pages)
          else _latent_reference)
    with nvtx_range(SCOPE_MLA_DECODE):
        return fn(q, c_new, kr_new, c_pages, kr_pages, page_table,
                  positions, scale=float(softmax_scale))
