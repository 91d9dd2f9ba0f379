"""Paged ResidualAttention kernels (TPU target): decode AND prefill.

The serving engine stores the disaggregated cache in page pools addressed by
block tables.  The dense kernels (residual_attention.py) assume the wrapper
gathered pages into contiguous views; THESE kernels consume the pools
directly — block tables ride in as scalar-prefetch operands and the kernel
copies the pages it needs from HBM itself.  This is the Pallas analogue of
SGLang's paged RadixAttention fused with ForkKV's on-chip reconstruction
(paper §5.3), and the serving path on TPU (DESIGN.md §3, §12, §13).  Every
grid here is compiled by Mosaic for a TPU v5e in
``tests/test_tpu_compile.py``.

Pool layouts (per layer; the pools stack a leading layer axis).  Each
is chosen so that HBM holds no lane padding and a page is one contiguous
copy:

* base K/V: ``(P, Hkv, page, D)`` — head-major, so one page of every head
  is one ``(Hkv, page, D)`` copy;
* int8 dequant scales: ``(P, Hkv, 1, page)`` f32 — one (1, page) row per
  page and head, applied to score columns / probabilities;
* residual K/V: ``(ceil(Pr / G), page, G·R)`` with ``G = res_group(R)`` —
  an rCache page is only R lanes wide, so ``G`` consecutive page ids share
  one lane-dense row (page id ``p`` owns lanes ``(p % G)·R ...`` of row
  ``p // G``).  The kernels fetch the row, zero the other pages' lanes and
  contract against the up-projection tiled ``G`` times.

The kernels take the pools stacked over layers, ``(L, ...)`` + the
shapes above, and read them at ``layer`` (a scalar-prefetch operand, so
one compiled kernel serves every layer of a step), so no layer slice is
copied out before a kernel reads it.

:func:`to_base_pool`, :func:`to_scale_pool`, :func:`to_res_pool` build these
from page-major arrays and :func:`gather_base`, :func:`gather_scale`,
:func:`gather_res` read block tables back into contiguous views — the XLA
mirror, the executor and the tests go through them, never through the
layout directly.

The blocked page walk (DESIGN.md §12).  One kernel body serves every grid:
grid ``(B, Hkv // hb, W // ppb)``, and each step attends one row's queries
for ``hb`` KV heads over a block of ``ppb`` pages:

* ``ppb = min(W, 256 // page)`` pages (:func:`block_pages`; the largest
  divisor of the table width ``W`` below that), so a step's score tile is
  256 keys wide — lane-dense — and the grid has ``W / ppb`` page steps,
  not ``W``;
* ``hb`` is every KV head while the heads' query rows fit
  (:func:`heads_per_step`: decode), else one (a 256-token prefill tile);
  with all heads a page is one contiguous copy, and its residual row is
  fetched, and RoPE's sin/cos computed, once for all heads;
* the block's pages are not contiguous in the pool, so the kernel copies
  them by hand (``make_async_copy`` from ``pl.ANY`` pools, one per page and
  stream) into a double-buffered VMEM block: each live step first starts
  the copies of the NEXT live step in grid order (the next block, the next
  head group's first, or the next live row's first) into the other half,
  then waits for its own and computes;
* dead blocks — wholly past ``kv_len``, or (``window > 0``) wholly before
  the earliest query row's window, or any block of a ``q_len = 0`` row —
  issue no copy and no FLOPs; inside a live block, table slots outside the
  live pages re-read a live page, and the per-token mask drops them;
* int8 scale rows are too narrow for a hand-issued copy, so each page slot
  of a block gets its own pipelined BlockSpec (same clamped table entry as
  its page) and the block's scale row is their lane concatenation.

Rows past ``q_len`` (and ``q_len = 0`` rows) are written as exact zeros;
accumulation is f32 with one online softmax (:func:`_softmax_update`).

Six grids, one jitted wrapper each (their names are the ops' names in a
device trace):

* :func:`paged_residual_attention_decode` — disaggregated (bCache + rCache
  with per-request B_k/B_v up-projections, ForkKV mode).  RoPE for the
  reconstructed K residual is computed *in kernel* from the logical
  position — no sin/cos tables in HBM.
* :func:`paged_attention_decode_base` — base-only (unified caches: the
  prefix / full_reuse baselines, or ForkKV serving base-model requests
  with no adapter).  Same walk, no residual stream.
* :func:`paged_residual_attention_prefill` — chunked prefill over the same
  pools: Q is a (chunk) tile per request with a causal mask inside the
  chunk; the mixed grid with ``q_len = kv_len - start``.
* :func:`paged_attention_prefill_base` — base-only chunked prefill.
* :func:`paged_residual_attention_mixed` — the unified grid (DESIGN.md
  §14): one launch serves rows of DIFFERENT q-lengths — decode rows
  (q_len=1) and chunked-prefill rows (q_len=chunk) side by side in the
  same batch.  Each row's q-length rides in as a scalar-prefetch operand;
  rows are padded to the tile's chunk width and the per-row mask
  ``rowidx < q_len`` kills padding rows.  This is what lets
  iteration-level continuous batching attend a mixed plan in ONE kernel
  launch instead of a prefill launch plus a decode launch.
* :func:`paged_attention_mixed_base` — base-only unified grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INIT = -1e30
LANES = 128


# --------------------------------------------------------------------------
# Pool layouts
# --------------------------------------------------------------------------
def res_group(rank: int) -> int:
    """rCache pages packed side by side in one lane-dense pool row."""
    return max(1, LANES // rank)


def res_pool_rows(num_pages: int, rank: int) -> int:
    """Rows of a residual pool holding ``num_pages`` rank-``rank`` pages."""
    g = res_group(rank)
    return -(-num_pages // g)


def to_base_pool(pages):
    """(..., P, page, Hkv, D) page-major -> (..., P, Hkv, page, D)."""
    return jnp.swapaxes(pages, -3, -2)


def to_scale_pool(scales):
    """(..., P, page, Hkv) per-token scales -> (..., P, Hkv, 1, page)."""
    return jnp.swapaxes(scales, -2, -1)[..., None, :]


def to_res_pool(pages):
    """(..., Pr, page, R) page-major -> (..., ceil(Pr/G), page, G·R)."""
    *lead, n, page, r = pages.shape
    g = res_group(r)
    rows = res_pool_rows(n, r)
    pad = [(0, 0)] * len(lead) + [(0, rows * g - n), (0, 0), (0, 0)]
    x = jnp.pad(pages, pad).reshape(*lead, rows, g, page, r)
    return jnp.swapaxes(x, -3, -2).reshape(*lead, rows, page, g * r)


def gather_base(pool, bt):
    """Block-table pages of a (P, Hkv, page, D) pool as
    (B, W·page, Hkv, D)."""
    x = jnp.swapaxes(pool[bt], -3, -2)              # (B, W, page, Hkv, D)
    return x.reshape(bt.shape[0], -1, *x.shape[-2:])


def gather_scale(pool, bt):
    """Block-table pages of a (P, Hkv, 1, page) scale pool as
    (B, W·page, Hkv)."""
    x = jnp.swapaxes(pool[bt][..., 0, :], -2, -1)   # (B, W, page, Hkv)
    return x.reshape(bt.shape[0], -1, x.shape[-1])


def gather_res(pool, bt, rank: int):
    """Block-table pages of a packed residual pool as (B, W·page, R)."""
    g = res_group(rank)
    rows = pool[bt // g]                             # (B, W, page, G·R)
    rows = rows.reshape(*rows.shape[:-1], g, rank)   # (B, W, page, G, R)
    sel = (bt % g)[:, :, None, None, None]
    x = jnp.take_along_axis(rows, sel, axis=3)[:, :, :, 0]
    return x.reshape(bt.shape[0], -1, rank)


def res_pages(pool, ids, rank: int):
    """Whole residual pages ``ids`` of a (L, rows, page, G·R) pool as
    (L, n, page, R) — the per-page view tiers and tests compare."""
    g = res_group(rank)
    ids = jnp.asarray(ids, jnp.int32)
    rows = pool[:, ids // g]                         # (L, n, page, G·R)
    rows = rows.reshape(*rows.shape[:-1], g, rank)
    sel = (ids % g)[None, :, None, None, None]
    return jnp.take_along_axis(rows, sel, axis=3)[:, :, :, 0]


def res_lane_index(ids, rank: int):
    """Pool coordinates of residual page ids (any shape S): their rows,
    shaped S + (1,), and their rank lanes, shaped S + (R,) — for
    scattering tokens or whole pages into a packed pool."""
    g = res_group(rank)
    return ((ids // g)[..., None],
            (ids % g)[..., None] * rank + jnp.arange(rank, dtype=jnp.int32))


def _res_lanes(b, d: int):
    """Up-projection (B, R, Hkv·D) -> (B, Hkv, G·R, D): B tiled over the
    ``G`` lane groups of a packed residual row, so a row whose other
    pages' lanes are zeroed contracts to exactly its own page's term."""
    bsz, rank = b.shape[:2]
    bt = b.reshape(bsz, rank, -1, d).transpose(0, 2, 1, 3)
    return jnp.tile(bt, (1, 1, res_group(rank), 1))


# --------------------------------------------------------------------------
# The blocked page walk: one body behind all six grids
# --------------------------------------------------------------------------
BLOCK_TOKENS = 256   # K/V tokens per grid step: a lane-dense score tile
HEAD_ROWS = 512      # query rows (all KV heads) one grid step may hold


def block_pages(page: int, width: int) -> int:
    """Pages per grid step: ``BLOCK_TOKENS // page``, cut to the largest
    divisor of the block-table width.  The executor's widths are powers
    of two (floor 4), so this is ``min(width, 256 // page)`` there."""
    target = max(1, BLOCK_TOKENS // page)
    return max(n for n in range(1, min(width, target) + 1) if width % n == 0)


def heads_per_step(hkv: int, rows: int) -> int:
    """KV heads one grid step covers: all of them while their query rows
    (``rows`` = g · q-tile each) fit ``HEAD_ROWS`` (decode), else one — a
    256-token prefill tile's accumulators for every head would crowd
    VMEM, and its score tiles keep the MXU busy with one head."""
    return hkv if hkv * rows <= HEAD_ROWS else 1


def _row(sc, r, *, decode: bool, residual: bool):
    """(kv_len, first query position, q_len) of row ``r`` from the
    scalar-prefetch refs ``sc`` (block tables first): a decode row's one
    query sits at ``kv_len - 1``; an empty row has ``q_len`` 0."""
    kvlen = sc[1 + residual][r]
    if decode:
        return kvlen, kvlen - 1, (kvlen > 0).astype(jnp.int32)
    return kvlen, sc[2 + residual][r], sc[3 + residual][r]


def _span(kvlen, start, qlen, *, page: int, window: int):
    """(live, first page, last page) of a row's walk: pages past kv_len,
    and (SWA) pages wholly before the earliest query row's window
    (``kpos >= start - window + 1``), are never copied nor computed.  An
    empty row's last page clamps to 0."""
    first = (jnp.maximum(start - (window - 1), 0) // page if window > 0
             else jnp.int32(0))
    return qlen > 0, first, jnp.maximum(kvlen - 1, 0) // page


def _rope_tables(pos0, n: int, d: int, rope_theta: float):
    """sin, cos of RoPE at logical positions ``pos0 ... pos0 + n - 1``,
    each (n, D/2) f32 — computed in kernel, no tables in HBM."""
    pos = (pos0 + jax.lax.broadcasted_iota(
        jnp.int32, (n, 1), 0)).astype(jnp.float32)
    half = d // 2
    freqs = 1.0 / (rope_theta ** (jax.lax.broadcasted_iota(
        jnp.int32, (1, half), 1).astype(jnp.float32) / half))
    ang = pos * freqs
    return jnp.sin(ang), jnp.cos(ang)


def _rope(x, sin, cos):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _bdot(x, y, contract_y: int):
    """Batched (over the leading head axis) f32 matmul: ``x`` (h, m, k)
    against ``y`` (h, k, n) (``contract_y=1``) or (h, n, k) (``2``)."""
    return jax.lax.dot_general(
        x, y, (((2,), (contract_y,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)


def _scores(q, k, ks, k_lora, scale: float):
    """(hb, rows, ppb·page) scores of f32 queries against a block's base
    keys ``k`` (hb, ppb·page, D).  An int8 block (``ks``, its (hb, 1,
    ppb·page) per-token scale rows) is dequantized on the score columns
    (DESIGN.md §18) before the full-precision residual term is added."""
    if ks is None:
        if k_lora is not None:
            k = k + k_lora
        return _bdot(q, k, 2) * scale
    s = _bdot(q, k, 2) * ks
    if k_lora is not None:
        s = s + _bdot(q, k_lora, 2)
    return s * scale


def _softmax_update(s, mask, m_scr, l_scr, acc_scr, v, vs=None):
    """One online-softmax step over a (hb, rows, ppb·page) score tile:
    rescales the running accumulators by alpha once per block and folds in
    the block's masked probabilities (an int8 V block's per-token scale
    rows multiply them).  Returns (p, alpha) for the residual
    accumulator, which the caller updates for all heads in one product."""
    s = jnp.where(mask, s, NEG_INIT)
    m_prev = m_scr[:, :, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new) * mask
    l_new = l_scr[:, :, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = p if vs is None else p * vs
    acc_scr[...] = acc_scr[...] * alpha + _bdot(pv, v, 1)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
    return p, alpha


def _kernel(*refs, decode: bool, residual: bool, quant: bool, page: int,
            ppb: int, hb: int, sq: int, window: int, scale: float,
            rank: int, rope_theta: float, use_rope: bool, nrows: int):
    """One grid step (row ``b``, head group ``hg``, page block ``j``): the
    ``ppb`` pages of block ``j`` for ``hb`` KV heads, copied by hand from
    the pools into one half of a double-buffered VMEM block while the
    other half is computed (module docstring)."""
    it = iter(refs)
    take = lambda n: [next(it) for _ in range(n)]  # noqa: E731
    sc = take(3 + residual + 2 * (not decode))
    bt_b, bt_r = sc[0], (sc[1] if residual else None)
    layer = sc[-1][0]
    q_ref, kb_hbm, vb_hbm = take(3)
    ks_refs, vs_refs = (take(ppb), take(ppb)) if quant else (None, None)
    kr_hbm, vr_hbm, bk_ref, bv_ref = take(4) if residual else [None] * 4
    out_ref, kb_buf, vb_buf = take(3)
    kr_buf, vr_buf = take(2) if residual else (None, None)
    sems, slot_ref, m_scr, l_scr, acc_scr = take(5)
    accr_scr, = take(1) if residual else (None,)

    b, hg, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nhg, nj = pl.num_programs(1), pl.num_programs(2)
    grp = res_group(rank) if residual else 1

    def row(r):
        return _row(sc, r, decode=decode, residual=residual)

    def span(r):
        return _span(*row(r), page=page, window=window)

    def next_row(after):
        """First row past ``after`` with a live walk (``nrows`` if none)."""
        def body(_, r):
            live_r = span(jnp.minimum(r, nrows - 1))[0]
            return jnp.where((r < nrows) & ~live_r, r + 1, r)
        return jax.lax.fori_loop(0, nrows, body, after + 1)

    streams = [(kb_hbm, kb_buf), (vb_hbm, vb_buf)]
    res_streams = [(kr_hbm, kr_buf), (vr_hbm, vr_buf)] if residual else []

    def each_page(r, blk, fn):
        """``fn(i, table slot)`` for the ``ppb`` page slots of block
        ``blk`` of row ``r``, each slot clamped to the live pages: slots
        past the last live page (or before the window) re-read a live
        page, whose tokens the mask drops."""
        _, first, last = span(r)

        def body(i, carry):
            fn(i, jnp.clip(blk * ppb + i, first, last))
            return carry
        jax.lax.fori_loop(0, ppb, body, 0)

    def copy(r, h, slot, op):
        """Per page slot: the DMAs (one per stream) that bring its page
        of row ``r``, head group ``h``, into buffer half ``slot``, each
        started or waited (``op``)."""
        heads = slice(None) if hb == kb_hbm.shape[2] else pl.ds(h * hb, hb)

        def fn(i, idx):
            pb = bt_b[r, idx]
            for s, (hbm, buf) in enumerate(streams):
                op(pltpu.make_async_copy(hbm.at[layer, pb, heads],
                                         buf.at[slot, :, i],
                                         sems.at[s, slot]))
            if residual:
                pr = bt_r[r, idx] // grp
                for s, (hbm, buf) in enumerate(res_streams, len(streams)):
                    op(pltpu.make_async_copy(hbm.at[layer, pr],
                                             buf.at[slot, i],
                                             sems.at[s, slot]))
        return fn

    def fetch(r, h, blk, slot):
        each_page(r, blk, copy(r, h, slot, lambda cp: cp.start()))

    def mask_residual(slot):
        """Zero the other pages' lanes of each copied residual row, in
        place: the block then contracts against the up-projection tiled
        ``G`` times to exactly its own pages' terms."""
        def fn(i, idx):
            lo = (bt_r[b, idx] % grp) * rank
            for buf in (kr_buf, vr_buf):
                x = buf[slot, i]
                lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
                buf[slot, i] = jnp.where((lane >= lo) & (lane < lo + rank),
                                         x, jnp.zeros_like(x))
        each_page(b, j, fn)

    kvlen, start, qlen = row(b)
    live, first, last = span(b)
    first_blk, last_blk = first // ppb, last // ppb

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INIT)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        if residual:
            accr_scr[...] = jnp.zeros_like(accr_scr)

    @pl.when((b == 0) & (hg == 0) & (j == 0))
    def _fetch_first():
        # the walk's first live block has no predecessor to fetch it
        slot_ref[0] = 0
        r0 = next_row(jnp.int32(-1))

        @pl.when(r0 < nrows)
        def _():
            fetch(r0, 0, span(r0)[1] // ppb, 0)

    @pl.when(live & (j >= first_blk) & (j <= last_blk))
    def _step():
        slot = slot_ref[0]
        # fetch the next live step of the grid order into the other half
        # (the next block, the next head group's first, or the next live
        # row's first) before computing this one
        at_end = j == last_blk
        more_heads = hg + 1 < nhg
        nr = jax.lax.cond(at_end & ~more_heads, lambda: next_row(b),
                          lambda: b)
        nh = jnp.where(at_end, jnp.where(more_heads, hg + 1, 0), hg)
        nblk = jnp.where(
            at_end, span(jnp.minimum(nr, nrows - 1))[1] // ppb, j + 1)

        @pl.when(nr < nrows)
        def _():
            fetch(nr, nh, nblk, 1 - slot)

        each_page(b, j, copy(b, hg, slot, lambda cp: cp.wait()))

        bk = ppb * page
        rows, d = q_ref.shape[2], q_ref.shape[3]
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        ridx = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) % sq
        rowpos = start + ridx
        mask = (kpos < kvlen) & (kpos <= rowpos) & (ridx < qlen)
        if window > 0:
            mask = mask & (kpos > rowpos - window)
        if residual:
            if grp > 1:
                mask_residual(slot)
            k_r = kr_buf[slot].astype(jnp.float32).reshape(bk, -1)
            v_r = vr_buf[slot].astype(jnp.float32).reshape(bk, -1)
            if use_rope:
                sin, cos = _rope_tables(j * bk, bk, d, rope_theta)
        q = q_ref[0].astype(jnp.float32)                    # (hb, rows, D)
        k = kb_buf[slot].astype(jnp.float32).reshape(hb, bk, d)
        v = vb_buf[slot].astype(jnp.float32).reshape(hb, bk, d)
        ks = vs = k_lora = None
        if quant:
            ks = jnp.concatenate([r[0, 0] for r in ks_refs], axis=-1)
            vs = jnp.concatenate([r[0, 0] for r in vs_refs], axis=-1)
        if residual:
            # RoPE(K_r B_k) for every head of the block, one product each
            k_lora = jnp.stack([jnp.dot(k_r, bk_ref[0, h].astype(jnp.float32),
                                        preferred_element_type=jnp.float32)
                                for h in range(hb)])
            if use_rope:
                k_lora = _rope(k_lora, sin, cos)
        p, alpha = _softmax_update(_scores(q, k, ks, k_lora, scale), mask,
                                   m_scr, l_scr, acc_scr, v, vs)
        if residual:
            # the residual V rows are shared by the heads: one product
            pr = jnp.dot(p.reshape(hb * rows, bk), v_r,
                         preferred_element_type=jnp.float32)
            accr_scr[...] = accr_scr[...] * alpha + pr.reshape(hb, rows, -1)
        slot_ref[0] = 1 - slot

    @pl.when(j == nj - 1)
    def _fini():
        rows = q_ref.shape[2]
        keep = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) % sq < qlen
        acc = acc_scr[...]
        if residual:
            acc = acc + _bdot(accr_scr[...], bv_ref[0].astype(jnp.float32), 1)
        l = jnp.maximum(l_scr[:, :, :1], 1e-20)
        out_ref[0] = jnp.where(keep, acc / l, 0.0).astype(out_ref.dtype)


def _walk(q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt_b, bt_r,
          kv_len, start, q_len, *, scale: float, window: int,
          rope_theta: float, use_rope: bool, kb_scale, vb_scale,
          layer: int, interpret: bool):
    """The one pallas_call behind the six public grids.  ``start is None``
    is decode: q (B, Hq, D), each row's one query at ``kv_len - 1``.
    Otherwise q is (B, sq, Hq, D) with per-row ``start`` and ``q_len``.
    ``kr_pool is None`` is a base-only walk; ``kb_scale`` marks int8 base
    pools."""
    decode, residual = start is None, kr_pool is not None
    quant = kb_scale is not None
    hkv, page = kb_pool.shape[2], kb_pool.shape[3]
    if decode:
        (bsz, hq, d), sq = q.shape, 1
        qt = q.reshape(bsz, hkv, hq // hkv, d)
    else:
        bsz, sq, hq, d = q.shape
        qt = q.reshape(bsz, sq, hkv, hq // hkv, d).transpose(
            0, 2, 3, 1, 4).reshape(bsz, hkv, -1, d)
    rows = qt.shape[2]
    width = bt_b.shape[1]
    ppb = block_pages(page, width)
    hb = heads_per_step(hkv, rows)

    def blk(shape):
        return pl.BlockSpec(shape, lambda b, h, j, *_: (b, h, 0, 0))

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    scalars = [bt_b] + ([bt_r] if residual else []) + [kv_len]
    if not decode:
        scalars += [start, q_len]
    scalars.append(jnp.reshape(layer, (1,)))
    in_specs = [blk((1, hb, rows, d)), hbm, hbm]
    operands = [qt, kb_pool, vb_pool]
    bufs = [pltpu.VMEM((2, hb, ppb, page, d), kb_pool.dtype),
            pltpu.VMEM((2, hb, ppb, page, d), vb_pool.dtype)]
    if quant:
        # a (1, page) scale row is too narrow for a hand-issued copy (the
        # pool's last dim is under one 128-lane tile), so each page slot
        # of a block gets its own pipelined BlockSpec: (hb, 1, page) rows
        # of that slot's page, fetched a step ahead by Pallas
        def scale_map(i):
            def index(b, h, j, *sc):
                _, first, last = _span(
                    *_row(sc, b, decode=decode, residual=residual),
                    page=page, window=window)
                jc = jnp.clip(j, first // ppb, last // ppb)
                slot = jnp.clip(jc * ppb + i, first, last)
                return (sc[-1][0], sc[0][b, slot], h, 0, 0)
            return index
        for pool in (kb_scale, vb_scale):
            in_specs += [pl.BlockSpec((1, 1, hb, 1, page), scale_map(i))
                         for i in range(ppb)]
            operands += [pool] * ppb
    rank = 0
    if residual:
        rank, gr = b_k.shape[1], kr_pool.shape[-1]
        in_specs += [hbm, hbm, blk((1, hb, gr, d)), blk((1, hb, gr, d))]
        operands += [kr_pool, vr_pool, _res_lanes(b_k, d),
                     _res_lanes(b_v, d)]
        bufs += [pltpu.VMEM((2, ppb, page, gr), kr_pool.dtype),
                 pltpu.VMEM((2, ppb, page, gr), vr_pool.dtype)]
    scratch = bufs + [
        pltpu.SemaphoreType.DMA((len(bufs), 2)),
        pltpu.SMEM((1,), jnp.int32),
        pltpu.VMEM((hb, rows, LANES), jnp.float32),
        pltpu.VMEM((hb, rows, LANES), jnp.float32),
        pltpu.VMEM((hb, rows, d), jnp.float32)]
    if residual:
        scratch.append(pltpu.VMEM((hb, rows, gr), jnp.float32))

    kernel = functools.partial(
        _kernel, decode=decode, residual=residual, quant=quant, page=page,
        ppb=ppb, hb=hb, sq=sq, window=window, scale=scale, rank=rank,
        rope_theta=rope_theta, use_rope=use_rope, nrows=bsz)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(bsz, hkv // hb, width // ppb),
            in_specs=in_specs,
            out_specs=blk((1, hb, rows, d)),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret,
    )(*(x.astype(jnp.int32) for x in scalars), *operands)
    if decode:
        return out.reshape(bsz, hq, d)
    return out.reshape(bsz, hkv, hq // hkv, sq, d).transpose(
        0, 3, 1, 2, 4).reshape(bsz, sq, hq, d)


_JIT_BASE = functools.partial(jax.jit, static_argnames=(
    "scale", "window", "interpret"))
_JIT = functools.partial(jax.jit, static_argnames=(
    "scale", "window", "rope_theta", "use_rope", "interpret"))


# --------------------------------------------------------------------------
# The six grids (their names are the ops' names in a device trace)
# --------------------------------------------------------------------------
@_JIT
def paged_residual_attention_decode(q, kb_pool, vb_pool, kr_pool, vr_pool,
                                    b_k, b_v, bt_b, bt_r, kv_len, *,
                                    scale: float, window: int = 0,
                                    rope_theta: float = 10_000.0,
                                    use_rope: bool = True,
                                    kb_scale=None, vb_scale=None,
                                    layer: int = 0,
                                    interpret: bool = True):
    """Decode over paged disaggregated caches.

    q:        (B, Hq, D)
    kb/vb:    (L, P, Hkv, page, D) base pools (K RoPE'd at write time)
    kr/vr:    (L, ceil(Pr/G), page, G·R) packed residual pools (no RoPE,
              scaled; see the module docstring)
    b_k/b_v:  (B, R, Hkv*D)      per-request up-projections
    bt_b/bt_r:(B, n_pages) int32 block tables (logical page -> pool page)
    kv_len:   (B,) valid tokens; ``window > 0`` restricts attention to the
    trailing ``window`` positions (SWA).  ``kb_scale``/``vb_scale``
    ((L, P, Hkv, 1, page) f32, or None) mark the base pools as
    int8-quantized: each block is dequantized in VMEM next to the
    running softmax (DESIGN.md §18).  Pools are read at
    ``layer``.  Returns (B, Hq, D).
    """
    return _walk(q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt_b,
                 bt_r, kv_len, None, None, scale=scale, window=window,
                 rope_theta=rope_theta, use_rope=use_rope,
                 kb_scale=kb_scale, vb_scale=vb_scale, layer=layer,
                 interpret=interpret)


@_JIT_BASE
def paged_attention_decode_base(q, kb_pool, vb_pool, bt_b, kv_len, *,
                                scale: float, window: int = 0,
                                kb_scale=None, vb_scale=None,
                                layer: int = 0,
                                interpret: bool = True):
    """Base-only paged decode: attention over the bCache pool alone.

    Serves the unified-cache baselines (prefix / full_reuse) and ForkKV
    requests without an adapter.  Same shapes as the disaggregated variant
    minus the residual stream:

    q: (B, Hq, D); kb/vb: (L, P, Hkv, page, D); bt_b: (B, n_pages);
    kv_len: (B,); kb_scale/vb_scale: (L, P, Hkv, 1, page) f32 int8 dequant
    scales, or None.  Returns (B, Hq, D).
    """
    return _walk(q, kb_pool, vb_pool, None, None, None, None, bt_b, None,
                 kv_len, None, None, scale=scale, window=window,
                 rope_theta=0.0, use_rope=False,
                 kb_scale=kb_scale, vb_scale=vb_scale, layer=layer,
                 interpret=interpret)


@_JIT
def paged_residual_attention_prefill(q, kb_pool, vb_pool, kr_pool, vr_pool,
                                     b_k, b_v, bt_b, bt_r, start, kv_len, *,
                                     scale: float, window: int = 0,
                                     rope_theta: float = 10_000.0,
                                     use_rope: bool = True,
                                     kb_scale=None, vb_scale=None,
                                     layer: int = 0,
                                     interpret: bool = True):
    """Chunked prefill over paged disaggregated caches (DESIGN.md §13).

    The chunk's own K/V must already be written into the pools (the
    executor writes before attending), so the causal mask inside the chunk
    is pure masking — no separate self-attention pass.

    q:        (B, chunk, Hq, D) RoPE'd queries
    kb/vb:    (L, P, Hkv, page, D) base pools;  kr/vr: packed residual pools
    b_k/b_v:  (B, R, Hkv*D) per-request up-projections
    bt_b/bt_r:(B, n_pages) block tables
    start:    (B,) absolute position of each chunk's first query row
    kv_len:   (B,) valid tokens incl. this chunk's writes (= start+n_valid;
              rows past kv_len-1 are padding and come back as zeros, the
              mixed grid's contract with ``q_len = kv_len - start``).
    Returns (B, chunk, Hq, D).
    """
    q_len = jnp.clip(kv_len - start, 0, q.shape[1])
    return _walk(q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt_b,
                 bt_r, kv_len, start, q_len, scale=scale, window=window,
                 rope_theta=rope_theta, use_rope=use_rope,
                 kb_scale=kb_scale, vb_scale=vb_scale, layer=layer,
                 interpret=interpret)


@_JIT_BASE
def paged_attention_prefill_base(q, kb_pool, vb_pool, bt_b, start, kv_len, *,
                                 scale: float, window: int = 0,
                                 kb_scale=None, vb_scale=None,
                                 layer: int = 0,
                                 interpret: bool = True):
    """Base-only chunked prefill: unified caches / no-LoRA requests, and
    the broadcast-fork base trajectory.  Shapes as the disaggregated
    variant minus the residual stream.  Returns (B, chunk, Hq, D)."""
    q_len = jnp.clip(kv_len - start, 0, q.shape[1])
    return _walk(q, kb_pool, vb_pool, None, None, None, None, bt_b, None,
                 kv_len, start, q_len, scale=scale, window=window,
                 rope_theta=0.0, use_rope=False,
                 kb_scale=kb_scale, vb_scale=vb_scale, layer=layer,
                 interpret=interpret)


@_JIT
def paged_residual_attention_mixed(q, kb_pool, vb_pool, kr_pool, vr_pool,
                                   b_k, b_v, bt_b, bt_r, start, q_len,
                                   kv_len, *, scale: float, window: int = 0,
                                   rope_theta: float = 10_000.0,
                                   use_rope: bool = True,
                                   kb_scale=None, vb_scale=None,
                                   layer: int = 0,
                                   interpret: bool = True):
    """Unified mixed prefill/decode grid over paged disaggregated caches.

    As :func:`paged_residual_attention_prefill`, except each row
    additionally carries ``q_len`` (B,) — its count of VALID query rows —
    as a scalar-prefetch operand: a decode row is ``q_len=1`` (its single
    query padded up to the tile's chunk width), a prefill row uses its
    whole chunk.  Rows past ``q_len`` produce exact zeros; ``q_len=0``
    rows (batch padding) skip all copies and FLOPs.  ``kv_len`` must
    equal ``start + q_len`` per row.  Returns (B, chunk, Hq, D).
    """
    return _walk(q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt_b,
                 bt_r, kv_len, start, q_len, scale=scale, window=window,
                 rope_theta=rope_theta, use_rope=use_rope,
                 kb_scale=kb_scale, vb_scale=vb_scale, layer=layer,
                 interpret=interpret)


@_JIT_BASE
def paged_attention_mixed_base(q, kb_pool, vb_pool, bt_b, start, q_len,
                               kv_len, *, scale: float, window: int = 0,
                               kb_scale=None, vb_scale=None,
                               layer: int = 0,
                               interpret: bool = True):
    """Base-only unified mixed grid: unified caches / no-LoRA requests.
    Shapes as :func:`paged_residual_attention_mixed` minus the residual
    stream.  Returns (B, chunk, Hq, D)."""
    return _walk(q, kb_pool, vb_pool, None, None, None, None, bt_b, None,
                 kv_len, start, q_len, scale=scale, window=window,
                 rope_theta=0.0, use_rope=False,
                 kb_scale=kb_scale, vb_scale=vb_scale, layer=layer,
                 interpret=interpret)
