"""Paged ResidualAttention kernels (TPU target): decode AND prefill.

The serving engine stores the disaggregated cache in page pools addressed by
block tables.  The dense kernels (residual_attention.py) assume the wrapper
gathered pages into contiguous views; THESE kernels consume the pools
directly — block tables ride in as scalar-prefetch operands and the
BlockSpec index maps dereference them, so each grid step DMA's exactly one
(page × kv_head) tile of bCache + one packed row of rCache from HBM.  This
is the Pallas analogue of SGLang's paged RadixAttention fused with ForkKV's
on-chip reconstruction (paper §5.3), and the serving path on TPU
(DESIGN.md §3, §12, §13).  Every grid here is compiled by Mosaic for a
TPU v5e at Llama3-8B widths in ``tests/test_tpu_compile.py``.

Pool layouts (per layer; the pools stack a leading layer axis).  Each
is chosen so that Mosaic accepts the per-step block (its last two dims
divisible by the (8, 128) tile or equal to the array's) and so that HBM
holds no lane padding:

* base K/V: ``(P, Hkv, page, D)`` — head-major, one (page, D) tile per
  grid step;
* int8 dequant scales: ``(P, Hkv, 1, page)`` f32 — one (1, page) row per
  step, applied to score columns / probabilities;
* residual K/V: ``(ceil(Pr / G), page, G·R)`` with ``G = res_group(R)`` —
  an rCache page is only R lanes wide, so ``G`` consecutive page ids share
  one lane-dense row (page id ``p`` owns lanes ``(p % G)·R ...`` of row
  ``p // G``).  The kernels fetch the row, zero the other pages' lanes and
  contract against the up-projection tiled ``G`` times.

The kernels take the pools stacked over layers, ``(L, ...)`` + the
shapes above, and read them at a static ``layer``, so no layer slice is
copied out before a kernel reads it.

:func:`to_base_pool`, :func:`to_scale_pool`, :func:`to_res_pool` build these
from page-major arrays and :func:`gather_base`, :func:`gather_scale`,
:func:`gather_res` read block tables back into contiguous views — the XLA
mirror, the executor and the tests go through them, never through the
layout directly.

Per-request page-count masking: the page axis of the grid is sized for the
widest request in the batch, but a request with ``kv_len`` tokens only has
``ceil(kv_len / page)`` live pages.  Grid steps past that point (a) clamp
their index maps to the request's last live page — the block index repeats,
so the Pallas pipeline skips the DMA re-fetch — and (b) skip the softmax
update entirely under ``pl.when``, so short requests pay FLOPs for their
own length, not the batch maximum.

Sliding windows (``window > 0``) clamp the page walk at BOTH ends: leading
pages entirely outside the attention window of the earliest query row are
clamped to the first in-window page (same repeated-block-index DMA skip)
and their FLOPs are skipped too, so a long-context SWA request pays for
``ceil(window/page) + 1`` trailing pages, not its whole history
(DESIGN.md §13).

Six variants:

* :func:`paged_residual_attention_decode` — disaggregated (bCache + rCache
  with per-request B_k/B_v up-projections, ForkKV mode).  RoPE for the
  reconstructed K residual is computed *in kernel* from the logical
  position (page_index·page_size + offset) — no sin/cos tables in HBM.
* :func:`paged_attention_decode_base` — base-only (unified caches: the
  prefix / full_reuse baselines, or ForkKV serving base-model requests
  with no adapter).  Same grid and skip logic, no residual stream.
* :func:`paged_residual_attention_prefill` — chunked prefill over the same
  pools: Q is a (chunk) tile per request, KV streams page by page with a
  causal mask inside the chunk and the running softmax carried across page
  steps in VMEM scratch.
* :func:`paged_attention_prefill_base` — base-only chunked prefill.
* :func:`paged_residual_attention_mixed` — the unified grid (DESIGN.md
  §14): one launch serves rows of DIFFERENT q-lengths — decode rows
  (q_len=1) and chunked-prefill rows (q_len=chunk) side by side in the
  same batch.  Each row's q-length rides in as a scalar-prefetch operand;
  rows are padded to the tile's chunk width and the per-row mask
  ``rowidx < q_len`` kills padding rows, whose outputs are written as
  exact zeros (deterministic across backends, unlike prefill's
  ignored-garbage rows).  This is what lets iteration-level continuous
  batching attend a mixed plan in ONE kernel launch instead of a prefill
  launch plus a decode launch.
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


def _res_lanes(rank: int, d: int):
    """Up-projection (B, R, Hkv·D) -> (B, Hkv, G·R, D): B tiled over the
    ``G`` lane groups of a packed residual row, so a row whose other
    pages' lanes are zeroed contracts to exactly its own page's term."""
    g = res_group(rank)

    def tile(b):
        bsz = b.shape[0]
        bt = b.reshape(bsz, rank, -1, d).transpose(0, 2, 1, 3)
        return jnp.tile(bt, (1, 1, g, 1))
    return tile


# --------------------------------------------------------------------------
# Shared kernel-body pieces
# --------------------------------------------------------------------------
def _last_live_page(kvl, page: int):
    """Index of the last page holding valid tokens (kv_len >= 1 assumed;
    clamps to page 0 for empty/padded rows)."""
    return jnp.maximum(kvl - 1, 0) // page


def _first_window_page(qpos_min, page: int, window: int):
    """Index of the first page intersecting the attention window of the
    earliest query row (``kpos >= qpos_min - window + 1``).  Only
    meaningful for ``window > 0``."""
    return jnp.maximum(qpos_min - (window - 1), 0) // page


def _res_tile(r_ref, rpage, rank: int):
    """Residual page ``rpage`` out of its packed (page, G·R) pool row: the
    row's other pages' lanes are zeroed.  Returns a (page, G·R) f32 tile."""
    x = r_ref[0, 0].astype(jnp.float32)
    lo = (rpage % res_group(rank)) * rank
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= lo) & (lane < lo + rank), x, 0.0)


def _residual_k(kr_ref, bk_ref, j, rpage, *, page: int, d: int, rank: int,
                rope_theta: float, use_rope: bool):
    """In-kernel K residual with deferred RoPE — shared by the
    disaggregated decode, prefill and mixed bodies so a numerics fix can
    never diverge them: RoPE(K_r B_k), with RoPE computed from the logical
    position (j·page + offset), no sin/cos tables in HBM.  Returns a
    (page, D) f32 tile."""
    k_r = _res_tile(kr_ref, rpage, rank)                       # (page, G·R)
    b_k = bk_ref[0, 0].astype(jnp.float32)                     # (G·R, D)
    k_lora = jnp.dot(k_r, b_k, preferred_element_type=jnp.float32)
    if use_rope:
        pos = (j * page + jax.lax.broadcasted_iota(
            jnp.int32, (page, 1), 0)).astype(jnp.float32)      # (page, 1)
        half = d // 2
        freqs = 1.0 / (rope_theta ** (jax.lax.broadcasted_iota(
            jnp.int32, (1, half), 1).astype(jnp.float32) / half))
        ang = pos * freqs                                      # (page, half)
        sin, cos = jnp.sin(ang), jnp.cos(ang)
        x1, x2 = k_lora[:, :half], k_lora[:, half:]
        k_lora = jnp.concatenate([x1 * cos - x2 * sin,
                                  x2 * cos + x1 * sin], axis=-1)
    return k_lora


def _scores(q, kb_ref, ks_ref, k_lora, scale: float):
    """(rows, page) scores of f32 queries against one bCache page tile.
    An int8 tile (``ks_ref`` given) is dequantized on the score columns
    with its (1, page) per-token scale row (DESIGN.md §18), before the
    full-precision residual term is added."""
    k_b = kb_ref[0, 0, 0].astype(jnp.float32)                  # (page, D)
    if ks_ref is None:
        k = k_b if k_lora is None else k_b + k_lora
        return jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    s = jnp.dot(q, k_b.T, preferred_element_type=jnp.float32) * ks_ref[
        0, 0, 0]
    if k_lora is not None:
        s = s + jnp.dot(q, k_lora.T, preferred_element_type=jnp.float32)
    return s * scale


def _softmax_update(s, mask, m_scr, l_scr, acc_scr, vb_ref, vs_ref=None,
                    accr_scr=None, v_r=None):
    """One online-softmax step over a (rows, page) score tile — the
    single implementation behind all six kernel bodies.  Rescales the
    running accumulators by alpha and folds in this page's masked probs;
    an int8 V tile's per-token scale row multiplies the probabilities, and
    the residual accumulator update is skipped for base-only kernels."""
    s = jnp.where(mask, s, NEG_INIT)
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new) * mask
    l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = p if vs_ref is None else p * vs_ref[0, 0, 0]
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        pv, vb_ref[0, 0, 0].astype(jnp.float32),
        preferred_element_type=jnp.float32)
    if accr_scr is not None:
        accr_scr[...] = accr_scr[...] * alpha + jnp.dot(
            p, v_r, preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)


def _chunk_rows(rows: int, chunk: int):
    """(rows, 1) in-chunk query index of each row of a (G·chunk, D) tile."""
    return jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) % chunk


def _kernel(bt_b_ref, bt_r_ref, kvlen_ref, q_ref, kb_ref, vb_ref, *rest,
            scale: float, page: int, window: int, rank: int,
            rope_theta: float, use_rope: bool, quant: bool = False):
    # ``quant`` is a trace-time static: the int8 variant threads two extra
    # scale operands right after the bCache tiles, so the ref list is
    # unpacked per-variant instead of duplicating the whole body.
    if quant:
        (ks_ref, vs_ref, kr_ref, vr_ref, bk_ref, bv_ref, out_ref,
         m_scr, l_scr, acc_scr, accr_scr) = rest
    else:
        (kr_ref, vr_ref, bk_ref, bv_ref, out_ref,
         m_scr, l_scr, acc_scr, accr_scr) = rest
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    d = q_ref.shape[3]
    kvlen = kvlen_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INIT)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        accr_scr[...] = jnp.zeros_like(accr_scr)

    # pages past ceil(kv_len/page) contribute nothing: skip their FLOPs
    # (their DMA is already skipped by the clamped index maps).  With a
    # sliding window the query sits at kvlen-1, so pages entirely before
    # kvlen - window are dead too (their DMA repeats the first in-window
    # page and is likewise skipped).  On a live step the clamp is the
    # identity, so page j's own table entry names the residual row.
    live = j * page < kvlen
    if window > 0:
        live = live & ((j + 1) * page > kvlen - window)

    @pl.when(live)
    def _compute():
        rpage = bt_r_ref[b, j]
        k_lora = _residual_k(kr_ref, bk_ref, j, rpage, page=page, d=d,
                             rank=rank, rope_theta=rope_theta,
                             use_rope=use_rope)
        q = q_ref[0, 0].astype(jnp.float32)                    # (G, D)
        s = _scores(q, kb_ref, ks_ref, k_lora, scale)
        kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        mask = kpos < kvlen
        if window > 0:
            mask = mask & (kpos > kvlen - 1 - window)
        _softmax_update(s, mask, m_scr, l_scr, acc_scr, vb_ref, vs_ref,
                        accr_scr, _res_tile(vr_ref, rpage, rank))

    @pl.when(j == nj - 1)
    def _fini():
        b_v = bv_ref[0, 0].astype(jnp.float32)
        acc = acc_scr[...] + jnp.dot(accr_scr[...], b_v,
                                     preferred_element_type=jnp.float32)
        l = jnp.maximum(l_scr[:, :1], 1e-20)
        out_ref[0, 0] = (acc / l).astype(out_ref.dtype)


def _decode_page_clamp(page: int, window: int):
    """Index-map page clamp for decode: dead grid steps repeat a live
    page's block index so the Pallas pipeline skips their DMA.  Trailing
    steps clamp to the last live page; with a sliding window, leading
    steps clamp to the first in-window page."""
    def clamp(j, kvl):
        jc = jnp.minimum(j, _last_live_page(kvl, page))
        if window > 0:
            jc = jnp.maximum(jc, _first_window_page(kvl - 1, page, window))
        return jc
    return clamp


@functools.partial(jax.jit, static_argnames=("scale", "window", "rope_theta",
                                             "use_rope", "layer",
                                             "interpret"))
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
    int8-quantized: each page tile is dequantized in VMEM next to the
    running softmax (DESIGN.md §18).  Pools are read at the static
    ``layer``.  Returns (B, Hq, D).
    """
    bsz, hq, d = q.shape
    hkv, page = kb_pool.shape[2], kb_pool.shape[3]
    g = hq // hkv
    r = b_k.shape[1]
    gr = kr_pool.shape[-1]
    grp = res_group(r)
    n_pages = bt_b.shape[1]
    quant = kb_scale is not None

    qt = q.reshape(bsz, hkv, g, d)
    bkt = _res_lanes(r, d)(b_k)
    bvt = _res_lanes(r, d)(b_v)

    kernel = functools.partial(_kernel, scale=scale, page=page,
                               window=window, rank=r, rope_theta=rope_theta,
                               use_rope=use_rope, quant=quant)

    clamp = _decode_page_clamp(page, window)

    def _b_map(b, h, j, btb, btr, kvl):
        return (layer, btb[b, clamp(j, kvl[b])], h, 0, 0)

    def _r_map(b, h, j, btb, btr, kvl):
        return (layer, btr[b, clamp(j, kvl[b])] // grp, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, g, d),
                     lambda b, h, j, btb, btr, kvl: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, 1, page, d), _b_map),
        pl.BlockSpec((1, 1, 1, page, d), _b_map),
    ]
    operands = [qt, kb_pool, vb_pool]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, 1, 1, page), _b_map),
                     pl.BlockSpec((1, 1, 1, 1, page), _b_map)]
        operands += [kb_scale, vb_scale]
    in_specs += [
        pl.BlockSpec((1, 1, page, gr), _r_map),
        pl.BlockSpec((1, 1, page, gr), _r_map),
        pl.BlockSpec((1, 1, gr, d),
                     lambda b, h, j, btb, btr, kvl: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, gr, d),
                     lambda b, h, j, btb, btr, kvl: (b, h, 0, 0)),
    ]
    operands += [kr_pool, vr_pool, bkt, bvt]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bsz, hkv, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda b, h, j, btb, btr, kvl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, gr), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, hkv, g, d), q.dtype),
        interpret=interpret,
    )(bt_b.astype(jnp.int32), bt_r.astype(jnp.int32),
      kv_len.astype(jnp.int32), *operands)
    return out.reshape(bsz, hq, d)


# --------------------------------------------------------------------------
# Base-only variant (unified caches / no-LoRA requests)
# --------------------------------------------------------------------------
def _kernel_base(bt_b_ref, kvlen_ref, q_ref, kb_ref, vb_ref, *rest,
                 scale: float, page: int, window: int,
                 quant: bool = False):
    if quant:
        ks_ref, vs_ref, out_ref, m_scr, l_scr, acc_scr = rest
    else:
        out_ref, m_scr, l_scr, acc_scr = rest
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    kvlen = kvlen_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INIT)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live = j * page < kvlen
    if window > 0:
        live = live & ((j + 1) * page > kvlen - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                    # (G, D)
        s = _scores(q, kb_ref, ks_ref, None, scale)
        kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        mask = kpos < kvlen
        if window > 0:
            mask = mask & (kpos > kvlen - 1 - window)
        _softmax_update(s, mask, m_scr, l_scr, acc_scr, vb_ref, vs_ref)

    @pl.when(j == nj - 1)
    def _fini():
        l = jnp.maximum(l_scr[:, :1], 1e-20)
        out_ref[0, 0] = (acc_scr[...] / l).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window", "layer",
                                             "interpret"))
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
    bsz, hq, d = q.shape
    hkv, page = kb_pool.shape[2], kb_pool.shape[3]
    g = hq // hkv
    n_pages = bt_b.shape[1]
    quant = kb_scale is not None
    qt = q.reshape(bsz, hkv, g, d)

    kernel = functools.partial(_kernel_base, scale=scale, page=page,
                               window=window, quant=quant)
    clamp = _decode_page_clamp(page, window)

    def _b_map(b, h, j, btb, kvl):
        return (layer, btb[b, clamp(j, kvl[b])], h, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, g, d),
                     lambda b, h, j, btb, kvl: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, 1, page, d), _b_map),
        pl.BlockSpec((1, 1, 1, page, d), _b_map),
    ]
    operands = [qt, kb_pool, vb_pool]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, 1, 1, page), _b_map),
                     pl.BlockSpec((1, 1, 1, 1, page), _b_map)]
        operands += [kb_scale, vb_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, hkv, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda b, h, j, btb, kvl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, hkv, g, d), q.dtype),
        interpret=interpret,
    )(bt_b.astype(jnp.int32), kv_len.astype(jnp.int32), *operands)
    return out.reshape(bsz, hq, d)


# --------------------------------------------------------------------------
# Chunked prefill variants (Q is a chunk tile, KV streams from the pools)
# --------------------------------------------------------------------------
def _prefill_page_clamp(page: int, window: int):
    """Index-map page clamp for prefill: trailing dead steps repeat the last
    live page; with a sliding window, leading steps repeat the first page
    that intersects the EARLIEST query row's window (``start``)."""
    def clamp(j, kvl, st):
        jc = jnp.minimum(j, _last_live_page(kvl, page))
        if window > 0:
            jc = jnp.maximum(jc, _first_window_page(st, page, window))
        return jc
    return clamp


def _kernel_prefill(bt_b_ref, bt_r_ref, kvlen_ref, start_ref, q_ref, kb_ref,
                    vb_ref, *rest, scale: float, page: int, window: int,
                    rank: int, rope_theta: float, use_rope: bool,
                    quant: bool = False):
    if quant:
        (ks_ref, vs_ref, kr_ref, vr_ref, bk_ref, bv_ref, out_ref,
         m_scr, l_scr, acc_scr, accr_scr) = rest
    else:
        (kr_ref, vr_ref, bk_ref, bv_ref, out_ref,
         m_scr, l_scr, acc_scr, accr_scr) = rest
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    g, chunk, d = q_ref.shape[2], q_ref.shape[3], q_ref.shape[4]
    rows = g * chunk
    kvlen = kvlen_ref[b]        # valid tokens INCLUDING this chunk's writes
    start = start_ref[b]        # absolute position of the chunk's first row

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INIT)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        accr_scr[...] = jnp.zeros_like(accr_scr)

    # dead pages: past the last live page, or (SWA) entirely before the
    # earliest query row's window.  Their DMA is skipped by the clamped
    # index maps; skip their FLOPs here.
    live = j * page < kvlen
    if window > 0:
        live = live & ((j + 1) * page > start - (window - 1))

    @pl.when(live)
    def _compute():
        rpage = bt_r_ref[b, j]
        k_lora = _residual_k(kr_ref, bk_ref, j, rpage, page=page, d=d,
                             rank=rank, rope_theta=rope_theta,
                             use_rope=use_rope)
        # causal chunk scores; the online softmax carries across page steps
        q = q_ref[0, 0].astype(jnp.float32).reshape(rows, d)   # (G*chunk, D)
        s = _scores(q, kb_ref, ks_ref, k_lora, scale)
        rowpos = start + _chunk_rows(rows, chunk)
        kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        mask = (kpos < kvlen) & (kpos <= rowpos)
        if window > 0:
            mask = mask & (kpos > rowpos - window)
        _softmax_update(s, mask, m_scr, l_scr, acc_scr, vb_ref, vs_ref,
                        accr_scr, _res_tile(vr_ref, rpage, rank))

    @pl.when(j == nj - 1)
    def _fini():
        b_v = bv_ref[0, 0].astype(jnp.float32)
        acc = acc_scr[...] + jnp.dot(accr_scr[...], b_v,
                                     preferred_element_type=jnp.float32)
        l = jnp.maximum(l_scr[:, :1], 1e-20)
        out_ref[0, 0] = (acc / l).reshape(g, chunk, d).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window", "rope_theta",
                                             "use_rope", "layer",
                                             "interpret"))
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
              rows past kv_len-1 are padding and produce garbage rows the
              caller must ignore).  Returns (B, chunk, Hq, D).
    """
    bsz, sq, hq, d = q.shape
    hkv, page = kb_pool.shape[2], kb_pool.shape[3]
    g = hq // hkv
    r = b_k.shape[1]
    gr = kr_pool.shape[-1]
    grp = res_group(r)
    n_pages = bt_b.shape[1]
    rows = g * sq
    quant = kb_scale is not None

    qt = q.reshape(bsz, sq, hkv, g, d).transpose(0, 2, 3, 1, 4)
    bkt = _res_lanes(r, d)(b_k)
    bvt = _res_lanes(r, d)(b_v)

    kernel = functools.partial(_kernel_prefill, scale=scale, page=page,
                               window=window, rank=r, rope_theta=rope_theta,
                               use_rope=use_rope, quant=quant)
    clamp = _prefill_page_clamp(page, window)

    def _b_map(b, h, j, btb, btr, kvl, st):
        return (layer, btb[b, clamp(j, kvl[b], st[b])], h, 0, 0)

    def _r_map(b, h, j, btb, btr, kvl, st):
        return (layer, btr[b, clamp(j, kvl[b], st[b])] // grp, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, g, sq, d),
                     lambda b, h, j, btb, btr, kvl, st: (b, h, 0, 0, 0)),
        pl.BlockSpec((1, 1, 1, page, d), _b_map),
        pl.BlockSpec((1, 1, 1, page, d), _b_map),
    ]
    operands = [qt, kb_pool, vb_pool]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, 1, 1, page), _b_map),
                     pl.BlockSpec((1, 1, 1, 1, page), _b_map)]
        operands += [kb_scale, vb_scale]
    in_specs += [
        pl.BlockSpec((1, 1, page, gr), _r_map),
        pl.BlockSpec((1, 1, page, gr), _r_map),
        pl.BlockSpec((1, 1, gr, d),
                     lambda b, h, j, btb, btr, kvl, st: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, gr, d),
                     lambda b, h, j, btb, btr, kvl, st: (b, h, 0, 0)),
    ]
    operands += [kr_pool, vr_pool, bkt, bvt]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(bsz, hkv, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, g, sq, d),
            lambda b, h, j, btb, btr, kvl, st: (b, h, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
            pltpu.VMEM((rows, gr), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, hkv, g, sq, d), q.dtype),
        interpret=interpret,
    )(bt_b.astype(jnp.int32), bt_r.astype(jnp.int32),
      kv_len.astype(jnp.int32), start.astype(jnp.int32), *operands)
    return out.transpose(0, 3, 1, 2, 4).reshape(bsz, sq, hq, d)


def _kernel_prefill_base(bt_b_ref, kvlen_ref, start_ref, q_ref, kb_ref,
                         vb_ref, *rest, scale: float, page: int,
                         window: int, quant: bool = False):
    if quant:
        ks_ref, vs_ref, out_ref, m_scr, l_scr, acc_scr = rest
    else:
        out_ref, m_scr, l_scr, acc_scr = rest
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    g, chunk, d = q_ref.shape[2], q_ref.shape[3], q_ref.shape[4]
    rows = g * chunk
    kvlen = kvlen_ref[b]
    start = start_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INIT)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live = j * page < kvlen
    if window > 0:
        live = live & ((j + 1) * page > start - (window - 1))

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32).reshape(rows, d)
        s = _scores(q, kb_ref, ks_ref, None, scale)
        rowpos = start + _chunk_rows(rows, chunk)
        kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        mask = (kpos < kvlen) & (kpos <= rowpos)
        if window > 0:
            mask = mask & (kpos > rowpos - window)
        _softmax_update(s, mask, m_scr, l_scr, acc_scr, vb_ref, vs_ref)

    @pl.when(j == nj - 1)
    def _fini():
        l = jnp.maximum(l_scr[:, :1], 1e-20)
        out_ref[0, 0] = (acc_scr[...] / l).reshape(
            g, chunk, d).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window", "layer",
                                             "interpret"))
def paged_attention_prefill_base(q, kb_pool, vb_pool, bt_b, start, kv_len, *,
                                 scale: float, window: int = 0,
                                 kb_scale=None, vb_scale=None,
                                 layer: int = 0,
                                 interpret: bool = True):
    """Base-only chunked prefill: unified caches / no-LoRA requests, and
    the broadcast-fork base trajectory.  Shapes as the disaggregated
    variant minus the residual stream.  Returns (B, chunk, Hq, D)."""
    bsz, sq, hq, d = q.shape
    hkv, page = kb_pool.shape[2], kb_pool.shape[3]
    g = hq // hkv
    n_pages = bt_b.shape[1]
    rows = g * sq
    quant = kb_scale is not None
    qt = q.reshape(bsz, sq, hkv, g, d).transpose(0, 2, 3, 1, 4)

    kernel = functools.partial(_kernel_prefill_base, scale=scale, page=page,
                               window=window, quant=quant)
    clamp = _prefill_page_clamp(page, window)

    def _b_map(b, h, j, btb, kvl, st):
        return (layer, btb[b, clamp(j, kvl[b], st[b])], h, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, g, sq, d),
                     lambda b, h, j, btb, kvl, st: (b, h, 0, 0, 0)),
        pl.BlockSpec((1, 1, 1, page, d), _b_map),
        pl.BlockSpec((1, 1, 1, page, d), _b_map),
    ]
    operands = [qt, kb_pool, vb_pool]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, 1, 1, page), _b_map),
                     pl.BlockSpec((1, 1, 1, 1, page), _b_map)]
        operands += [kb_scale, vb_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bsz, hkv, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, g, sq, d),
            lambda b, h, j, btb, kvl, st: (b, h, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, hkv, g, sq, d), q.dtype),
        interpret=interpret,
    )(bt_b.astype(jnp.int32), kv_len.astype(jnp.int32),
      start.astype(jnp.int32), *operands)
    return out.transpose(0, 3, 1, 2, 4).reshape(bsz, sq, hq, d)


# --------------------------------------------------------------------------
# Unified mixed prefill/decode grid (DESIGN.md §14)
# --------------------------------------------------------------------------
def _kernel_mixed(bt_b_ref, bt_r_ref, kvlen_ref, start_ref, qlen_ref, q_ref,
                  kb_ref, vb_ref, *rest, scale: float, page: int, window: int,
                  rank: int, rope_theta: float, use_rope: bool,
                  quant: bool = False):
    """Prefill kernel body generalized with a per-row q-length: rows past
    ``q_len`` are masked everywhere and written out as zeros, and rows
    with ``q_len == 0`` (batch padding) skip every page's FLOPs."""
    if quant:
        (ks_ref, vs_ref, kr_ref, vr_ref, bk_ref, bv_ref, out_ref,
         m_scr, l_scr, acc_scr, accr_scr) = rest
    else:
        (kr_ref, vr_ref, bk_ref, bv_ref, out_ref,
         m_scr, l_scr, acc_scr, accr_scr) = rest
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    g, chunk, d = q_ref.shape[2], q_ref.shape[3], q_ref.shape[4]
    rows = g * chunk
    kvlen = kvlen_ref[b]        # valid tokens INCLUDING this row's writes
    start = start_ref[b]        # absolute position of the row's first query
    qlen = qlen_ref[b]          # valid query rows (1 = decode, chunk = full)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INIT)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        accr_scr[...] = jnp.zeros_like(accr_scr)

    live = (qlen > 0) & (j * page < kvlen)
    if window > 0:
        live = live & ((j + 1) * page > start - (window - 1))

    @pl.when(live)
    def _compute():
        rpage = bt_r_ref[b, j]
        k_lora = _residual_k(kr_ref, bk_ref, j, rpage, page=page, d=d,
                             rank=rank, rope_theta=rope_theta,
                             use_rope=use_rope)
        q = q_ref[0, 0].astype(jnp.float32).reshape(rows, d)
        s = _scores(q, kb_ref, ks_ref, k_lora, scale)
        rowidx = _chunk_rows(rows, chunk)
        rowpos = start + rowidx
        kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        mask = (kpos < kvlen) & (kpos <= rowpos) & (rowidx < qlen)
        if window > 0:
            mask = mask & (kpos > rowpos - window)
        _softmax_update(s, mask, m_scr, l_scr, acc_scr, vb_ref, vs_ref,
                        accr_scr, _res_tile(vr_ref, rpage, rank))

    @pl.when(j == nj - 1)
    def _fini():
        b_v = bv_ref[0, 0].astype(jnp.float32)
        acc = acc_scr[...] + jnp.dot(accr_scr[...], b_v,
                                     preferred_element_type=jnp.float32)
        l = jnp.maximum(l_scr[:, :1], 1e-20)
        out = jnp.where(_chunk_rows(rows, chunk) < qlen, acc / l, 0.0)
        out_ref[0, 0] = out.reshape(g, chunk, d).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window", "rope_theta",
                                             "use_rope", "layer",
                                             "interpret"))
def paged_residual_attention_mixed(q, kb_pool, vb_pool, kr_pool, vr_pool,
                                   b_k, b_v, bt_b, bt_r, start, q_len,
                                   kv_len, *, scale: float, window: int = 0,
                                   rope_theta: float = 10_000.0,
                                   use_rope: bool = True,
                                   kb_scale=None, vb_scale=None,
                                   layer: int = 0,
                                   interpret: bool = True):
    """Unified mixed prefill/decode grid over paged disaggregated caches.

    Identical to :func:`paged_residual_attention_prefill` except each row
    additionally carries ``q_len`` (B,) — its count of VALID query rows —
    as a scalar-prefetch operand: a decode row is ``q_len=1`` (its single
    query padded up to the tile's chunk width), a prefill row uses its
    whole chunk.  Rows past ``q_len`` produce exact zeros; ``q_len=0``
    rows (batch padding) skip all FLOPs.  ``kv_len`` must equal
    ``start + q_len`` per row.  Returns (B, chunk, Hq, D).
    """
    bsz, sq, hq, d = q.shape
    hkv, page = kb_pool.shape[2], kb_pool.shape[3]
    g = hq // hkv
    r = b_k.shape[1]
    gr = kr_pool.shape[-1]
    grp = res_group(r)
    n_pages = bt_b.shape[1]
    rows = g * sq
    quant = kb_scale is not None

    qt = q.reshape(bsz, sq, hkv, g, d).transpose(0, 2, 3, 1, 4)
    bkt = _res_lanes(r, d)(b_k)
    bvt = _res_lanes(r, d)(b_v)

    kernel = functools.partial(_kernel_mixed, scale=scale, page=page,
                               window=window, rank=r, rope_theta=rope_theta,
                               use_rope=use_rope, quant=quant)
    clamp = _prefill_page_clamp(page, window)

    def _b_map(b, h, j, btb, btr, kvl, st, ql):
        return (layer, btb[b, clamp(j, kvl[b], st[b])], h, 0, 0)

    def _r_map(b, h, j, btb, btr, kvl, st, ql):
        return (layer, btr[b, clamp(j, kvl[b], st[b])] // grp, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, g, sq, d),
                     lambda b, h, j, btb, btr, kvl, st, ql:
                     (b, h, 0, 0, 0)),
        pl.BlockSpec((1, 1, 1, page, d), _b_map),
        pl.BlockSpec((1, 1, 1, page, d), _b_map),
    ]
    operands = [qt, kb_pool, vb_pool]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, 1, 1, page), _b_map),
                     pl.BlockSpec((1, 1, 1, 1, page), _b_map)]
        operands += [kb_scale, vb_scale]
    in_specs += [
        pl.BlockSpec((1, 1, page, gr), _r_map),
        pl.BlockSpec((1, 1, page, gr), _r_map),
        pl.BlockSpec((1, 1, gr, d),
                     lambda b, h, j, btb, btr, kvl, st, ql: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, gr, d),
                     lambda b, h, j, btb, btr, kvl, st, ql: (b, h, 0, 0)),
    ]
    operands += [kr_pool, vr_pool, bkt, bvt]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(bsz, hkv, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, g, sq, d),
            lambda b, h, j, btb, btr, kvl, st, ql: (b, h, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
            pltpu.VMEM((rows, gr), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, hkv, g, sq, d), q.dtype),
        interpret=interpret,
    )(bt_b.astype(jnp.int32), bt_r.astype(jnp.int32),
      kv_len.astype(jnp.int32), start.astype(jnp.int32),
      q_len.astype(jnp.int32), *operands)
    return out.transpose(0, 3, 1, 2, 4).reshape(bsz, sq, hq, d)


def _kernel_mixed_base(bt_b_ref, kvlen_ref, start_ref, qlen_ref, q_ref,
                       kb_ref, vb_ref, *rest, scale: float, page: int,
                       window: int, quant: bool = False):
    if quant:
        ks_ref, vs_ref, out_ref, m_scr, l_scr, acc_scr = rest
    else:
        out_ref, m_scr, l_scr, acc_scr = rest
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    g, chunk, d = q_ref.shape[2], q_ref.shape[3], q_ref.shape[4]
    rows = g * chunk
    kvlen = kvlen_ref[b]
    start = start_ref[b]
    qlen = qlen_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INIT)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live = (qlen > 0) & (j * page < kvlen)
    if window > 0:
        live = live & ((j + 1) * page > start - (window - 1))

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32).reshape(rows, d)
        s = _scores(q, kb_ref, ks_ref, None, scale)
        rowidx = _chunk_rows(rows, chunk)
        rowpos = start + rowidx
        kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        mask = (kpos < kvlen) & (kpos <= rowpos) & (rowidx < qlen)
        if window > 0:
            mask = mask & (kpos > rowpos - window)
        _softmax_update(s, mask, m_scr, l_scr, acc_scr, vb_ref, vs_ref)

    @pl.when(j == nj - 1)
    def _fini():
        l = jnp.maximum(l_scr[:, :1], 1e-20)
        out = jnp.where(_chunk_rows(rows, chunk) < qlen, acc_scr[...] / l,
                        0.0)
        out_ref[0, 0] = out.reshape(g, chunk, d).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window", "layer",
                                             "interpret"))
def paged_attention_mixed_base(q, kb_pool, vb_pool, bt_b, start, q_len,
                               kv_len, *, scale: float, window: int = 0,
                               kb_scale=None, vb_scale=None,
                               layer: int = 0,
                               interpret: bool = True):
    """Base-only unified mixed grid: unified caches / no-LoRA requests.
    Shapes as :func:`paged_residual_attention_mixed` minus the residual
    stream.  Returns (B, chunk, Hq, D)."""
    bsz, sq, hq, d = q.shape
    hkv, page = kb_pool.shape[2], kb_pool.shape[3]
    g = hq // hkv
    n_pages = bt_b.shape[1]
    rows = g * sq
    quant = kb_scale is not None
    qt = q.reshape(bsz, sq, hkv, g, d).transpose(0, 2, 3, 1, 4)

    kernel = functools.partial(_kernel_mixed_base, scale=scale, page=page,
                               window=window, quant=quant)
    clamp = _prefill_page_clamp(page, window)

    def _b_map(b, h, j, btb, kvl, st, ql):
        return (layer, btb[b, clamp(j, kvl[b], st[b])], h, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, g, sq, d),
                     lambda b, h, j, btb, kvl, st, ql: (b, h, 0, 0, 0)),
        pl.BlockSpec((1, 1, 1, page, d), _b_map),
        pl.BlockSpec((1, 1, 1, page, d), _b_map),
    ]
    operands = [qt, kb_pool, vb_pool]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, 1, 1, page), _b_map),
                     pl.BlockSpec((1, 1, 1, 1, page), _b_map)]
        operands += [kb_scale, vb_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(bsz, hkv, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, g, sq, d),
            lambda b, h, j, btb, kvl, st, ql: (b, h, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, hkv, g, sq, d), q.dtype),
        interpret=interpret,
    )(bt_b.astype(jnp.int32), kv_len.astype(jnp.int32),
      start.astype(jnp.int32), q_len.astype(jnp.int32), *operands)
    return out.transpose(0, 3, 1, 2, 4).reshape(bsz, sq, hq, d)
