"""Paged model executor: jit'd prefill/decode over pooled KV pages.

The pools are jnp arrays with a leading layer axis in the paged kernels'
storage layouts (``kernels/paged_residual_attention.py``: head-major base
pages, lane-packed residual pages); requests address them through block
tables of page ids.  In ForkKV mode two pools exist — the shared bCache
pool and the per-agent rCache pool — and attention runs over the
disaggregated layout.  Weights and adapters are arguments of every jitted
step, never constants captured in it, so a compiled step's size does not
depend on the model's.

Decode AND prefill are page-native (DESIGN.md §12/§13): the jitted steps
hand the pools and per-request block tables straight to the
``paged_residual_attention`` / ``paged_residual_attention_prefill``
dispatchers (``kernels/ops.py``) — the Pallas kernels on TPU, their XLA
gather mirrors elsewhere — so HBM traffic scales with each request's
actual ``kv_len`` instead of the engine-wide ``smax``.  Sliding-window
models run through the same kernels (the page walk clamps to the trailing
``ceil(window/page)+1`` pages).  The legacy gather-to-contiguous paths
survive behind ``ServeConfig.use_paged_kernel = False`` for bit-parity
testing; every executor call that takes them increments
``fallback_gather_calls`` so any remaining fallback is visible in
``Engine.metrics()``.  Compiled shapes are bucketed: batches pad to the
next power of two (capped at ``max_batch`` / the prefill plan) and paged
block-table widths to the next power of two of the batch's live page
count (floor ``ServeConfig.min_table_pages``), so the number of compiled
variants stays logarithmic under fluctuating load instead of retracing
per batch size.

Prefill is batched: ``prefill_batch`` packs several requests' chunks into
one padded ``(B, chunk)`` call (the engine schedules co-resident chunks
under the ``max_prefill_tokens`` budget).  Executor methods return DEVICE
arrays — no host syncs here; the engine blocks once per step.

CoW discipline: prefill never writes to inherited (shared) pages — the
engine passes the reserved DUMP page as the write target for positions
whose cache is inherited, so parent pages stay read-only.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import ModelConfig, ServeConfig
from repro.kernels import ops as kernel_ops
from repro.kernels import paged_residual_attention as pra
from repro.models import base
from repro.models import transformer as tfm
from repro.serving import trace
from repro.serving.sampling import sample_tokens

Params = Dict


def _pow2(n: int) -> int:
    """Smallest power of two >= n (>= 1)."""
    return 1 << max(0, n - 1).bit_length()


def _per_row(values, default, n: int, bpad: int) -> list:
    """``values`` (``default`` for each of the ``n`` live rows when None),
    padded with ``default`` to ``bpad`` rows."""
    vals = list(values) if values is not None else [default] * n
    return vals + [default] * (bpad - n)


def _i32(values) -> jnp.ndarray:
    return jnp.asarray(values, jnp.int32)


class Pools(NamedTuple):
    kb: jnp.ndarray          # (L, Pb, Hkv, page, hd)  base K (RoPE'd)
    vb: jnp.ndarray          # (L, Pb, Hkv, page, hd)  base V
    # residual K (no RoPE) / V: (L, ceil(Pr/G), page, G·R), G rank-R pages
    # packed per lane-dense row (pra.res_group)
    kr: Optional[jnp.ndarray]
    vr: Optional[jnp.ndarray]
    # int8 bCache pages (ModelConfig.kv_quant == "int8"): per-token-per-head
    # f32 dequant scales, written alongside every kb/vb write.  None on the
    # full-precision path; the rCache is rank-r and stays unquantized.
    kb_s: Optional[jnp.ndarray] = None   # (L, Pb, Hkv, 1, page)
    vb_s: Optional[jnp.ndarray] = None


def make_pools(cfg: ModelConfig, num_pages: int, num_res_pages: int,
               page_size: int, disagg: bool, dtype=None) -> Pools:
    dt = dtype or cfg.activation_dtype
    L, hd = cfg.num_layers, cfg.resolved_head_dim
    r = cfg.lora.rank
    quant = getattr(cfg, "kv_quant", "none") == "int8"
    kb = jnp.zeros((L, num_pages, cfg.num_kv_heads, page_size, hd),
                   jnp.int8 if quant else dt)
    vb = jnp.zeros_like(kb)
    if disagg:
        kr = jnp.zeros((L, pra.res_pool_rows(num_res_pages, r), page_size,
                        pra.res_group(r) * r), dt)
        vr = jnp.zeros_like(kr)
    else:
        kr = vr = None
    kb_s = vb_s = None
    if quant:
        kb_s = jnp.zeros((L, num_pages, cfg.num_kv_heads, 1, page_size),
                         jnp.float32)
        vb_s = jnp.zeros_like(kb_s)
    return Pools(kb, vb, kr, vr, kb_s, vb_s)


def write_tokens(pools: Pools, li: int, wp_b, wp_r, woff, kb_, vb_, ks_,
                 vs_, kr_, vr_, rank: int) -> Pools:
    """Scatter new tokens' K/V into layer ``li`` of the pools.

    ``wp_b``/``wp_r``/``woff`` index the tokens (any common leading shape
    ``T``, broadcastable); ``kb_``/``vb_``: T + (Hkv, hd); ``ks_``/``vs_``:
    T + (Hkv,) int8 scales or None; ``kr_``/``vr_``: T + (R,) residuals,
    or None when the pools hold no rCache.  Every index is spelled out
    down to the head, so each scattered window is one contiguous head_dim
    row and XLA keeps the pools in the layout the kernels read: a
    head-slice window makes it relayout (copy) whole pools per layer."""
    heads = jnp.arange(kb_.shape[-2], dtype=jnp.int32)
    at = (li, wp_b[..., None], heads, woff[..., None])
    kb = pools.kb.at[at].set(kb_)
    vb = pools.vb.at[at].set(vb_)
    ks, vs = pools.kb_s, pools.vb_s
    if ks_ is not None:
        at = (li, wp_b[..., None], heads, 0, woff[..., None])
        ks = ks.at[at].set(ks_)
        vs = vs.at[at].set(vs_)
    kr, vr = pools.kr, pools.vr
    if kr_ is not None:
        rows, lanes = pra.res_lane_index(wp_r, rank)
        kr = kr.at[li, rows, woff[..., None], lanes].set(kr_)
        vr = vr.at[li, rows, woff[..., None], lanes].set(vr_)
    return Pools(kb, vb, kr, vr, ks, vs)


def pool_bytes(pools: Pools) -> Dict[str, int]:
    out = {"base": int(pools.kb.nbytes + pools.vb.nbytes)}
    if pools.kb_s is not None:
        out["base"] += int(pools.kb_s.nbytes + pools.vb_s.nbytes)
    out["residual"] = int(pools.kr.nbytes + pools.vr.nbytes) \
        if pools.kr is not None else 0
    return out


class PagedExecutor:
    """Compiled paged prefill/decode for llama-family models."""

    def __init__(self, cfg: ModelConfig, params: Params,
                 lora: Optional[Params], serve_cfg: ServeConfig,
                 disagg: bool, max_pages_per_req: int):
        self.cfg = cfg
        self.params = params
        self.lora = lora
        self.sc = serve_cfg
        self.disagg = disagg and lora is not None
        self.page = serve_cfg.page_size
        self.max_pages_per_req = max_pages_per_req
        self.smax = max_pages_per_req * self.page
        # page-native serving: pools + block tables straight into the
        # kernel dispatchers for decode AND chunked prefill; SWA models
        # run the same kernels with window-clamped page walks (§13).
        self.use_paged = serve_cfg.use_paged_kernel
        self.min_table_pages = serve_cfg.min_table_pages
        # int8 bCache paging (DESIGN.md §18): quantize at write time,
        # dequantize per page tile inside the kernels / at the gather
        self.kv_quant = getattr(cfg, "kv_quant", "none") == "int8"
        # executor calls that took a legacy gather-to-contiguous path —
        # the acceptance probe for "zero gather copies" (0 whenever
        # use_paged_kernel=True; surfaced via Engine.metrics())
        self.fallback_gather_calls = 0
        # per-path shape counters and host time of each call (trace.py)
        self.counters = trace.ExecCounters()
        res_factor = max(1, cfg.kv_dim // max(cfg.lora.rank, 1))             if self.disagg else 1
        self.num_res_pages = serve_cfg.max_pages * res_factor             if self.disagg else serve_cfg.max_pages
        self.pools = make_pools(cfg, serve_cfg.max_pages,
                                self.num_res_pages, self.page, self.disagg)
        # reserved scratch pages (the engine overwrites these with the pages
        # it actually allocated); residual pool has its OWN dump page
        self.dump_page = serve_cfg.max_pages - 1
        self.dump_page_r = self.num_res_pages - 1
        # ``sampled`` is static: all-greedy batches (the default) compile
        # the seed's pure-argmax body with the sampling math dead-code
        # eliminated; a second variant exists only once sampling is used
        # weights ride in as arguments 0-1 and the donated pools as 2
        self._decode = jax.jit(self._decode_fn, donate_argnums=(2,),
                               static_argnames=("sampled",))
        self._prefill = jax.jit(self._prefill_fn, donate_argnums=(2,),
                                static_argnames=("chunk", "sampled",
                                                 "unified", "verify"))

    # ------------------------------------------------ tiered KV offload
    def export_pages(self, kind: str,
                     page_ids: Sequence[int]) -> List[Dict]:
        """Device→host copy of whole KV pages (DESIGN.md §10).

        ``kind`` selects the pool ("base" → kb/vb, "res" → kr/vr).  Returns
        one blob per page — ``{"k": (L, ...), "v": ...}`` numpy arrays
        holding the exact bytes of one page (head-major base pages, or a
        residual page's own (page, R) lanes), so a later
        :meth:`import_pages` restores the cache bit-identically.
        """
        ids = jnp.asarray(list(page_ids), jnp.int32)
        if kind == "base":
            karr = np.asarray(self.pools.kb[:, ids])     # (L, n, Hkv, ...)
            varr = np.asarray(self.pools.vb[:, ids])
        else:
            r = self.cfg.lora.rank
            karr = np.asarray(pra.res_pages(self.pools.kr, ids, r))
            varr = np.asarray(pra.res_pages(self.pools.vr, ids, r))
        # per-page COPIES, not views: each blob must be independently
        # freeable or the HostTier's byte accounting undercounts (a
        # surviving 1-page view would pin the whole n-page export)
        blobs = [{"k": karr[:, i].copy(), "v": varr[:, i].copy()}
                 for i in range(len(page_ids))]
        if kind == "base" and self.kv_quant:
            # int8 pages travel with their dequant scales so a round trip
            # through host/disk restores the cache bit-identically
            ksarr = np.asarray(self.pools.kb_s[:, ids])
            vsarr = np.asarray(self.pools.vb_s[:, ids])
            for i, b in enumerate(blobs):
                b["ks"] = ksarr[:, i].copy()
                b["vs"] = vsarr[:, i].copy()
        return blobs

    def import_pages(self, kind: str, page_ids: Sequence[int],
                     blobs: Sequence[Dict]) -> None:
        """Host→device copy: write blobs back into freshly allocated pages
        (the promotion half of the tier lifecycle).

        The scatter runs jitted with the pools donated, so XLA updates the
        pool buffers in place — O(pages promoted), not a copy of the whole
        pool.  Page counts are bucketed to powers of two (padding repeats
        page 0 with its own blob: duplicate index, identical value) so the
        number of compiled variants stays logarithmic.
        """
        n = len(page_ids)
        npad = _pow2(n)
        ids = list(page_ids) + [page_ids[0]] * (npad - n)
        blobs = list(blobs) + [blobs[0]] * (npad - n)
        k = jnp.asarray(np.stack([b["k"] for b in blobs], axis=1))
        v = jnp.asarray(np.stack([b["v"] for b in blobs], axis=1))
        quant = kind == "base" and self.kv_quant
        key = (kind, npad)
        if not hasattr(self, "_import_jit"):
            self._import_jit = {}
        if key not in self._import_jit:
            if quant:
                def fn(pools, ids_, k_, v_, ks_, vs_):
                    return pools._replace(
                        kb=pools.kb.at[:, ids_].set(k_),
                        vb=pools.vb.at[:, ids_].set(v_),
                        kb_s=pools.kb_s.at[:, ids_].set(ks_),
                        vb_s=pools.vb_s.at[:, ids_].set(vs_))
            elif kind == "base":
                def fn(pools, ids_, k_, v_):
                    return pools._replace(
                        kb=pools.kb.at[:, ids_].set(k_),
                        vb=pools.vb.at[:, ids_].set(v_))
            else:
                rank = self.cfg.lora.rank

                def fn(pools, ids_, k_, v_):
                    # (L, n, page, R) blobs into each page's own lanes
                    rows, lanes = pra.res_lane_index(ids_, rank)
                    li = jnp.arange(k_.shape[0])[:, None, None, None]
                    t = jnp.arange(k_.shape[2])[None, None, :, None]
                    at = (li, rows[None, :, None], t, lanes[None, :, None])
                    return pools._replace(kr=pools.kr.at[at].set(k_),
                                          vr=pools.vr.at[at].set(v_))
            self._import_jit[key] = jax.jit(fn, donate_argnums=(0,))
        if quant:
            ks = jnp.asarray(np.stack([b["ks"] for b in blobs], axis=1))
            vs = jnp.asarray(np.stack([b["vs"] for b in blobs], axis=1))
            self.pools = self._import_jit[key](
                self.pools, jnp.asarray(ids, jnp.int32), k, v, ks, vs)
        else:
            self.pools = self._import_jit[key](
                self.pools, jnp.asarray(ids, jnp.int32), k, v)

    # ------------------------------------------------------------ helpers
    @staticmethod
    def _layer(tree, li):
        """Layer ``li`` of a stacked (L, ...) pytree (None passes)."""
        if tree is None:
            return None
        return jax.tree_util.tree_map(lambda t: t[li], tree)

    def _project_kv(self, p_l, lora_l, h, sin, cos, adapter_ids):
        cfg = self.cfg
        hd = cfg.resolved_head_dim
        bsz, s, _ = h.shape
        k_base = (h @ p_l["wk"]).reshape(bsz, s, cfg.num_kv_heads, hd)
        v_base = (h @ p_l["wv"]).reshape(bsz, s, cfg.num_kv_heads, hd)
        if cfg.use_rope:
            from repro.core import rope as rope_lib
            k_base = rope_lib.apply_rope(k_base, sin, cos)
        if self.disagg:
            k_res = tfm._bgmv_down(h, lora_l["a_k"], lora_l["scaling"],
                                   adapter_ids)
            v_res = tfm._bgmv_down(h, lora_l["a_v"], lora_l["scaling"],
                                   adapter_ids)
            bk = lora_l["b_k"][adapter_ids]
            bv = lora_l["b_v"][adapter_ids]
            return k_base, v_base, k_res, v_res, bk, bv
        if lora_l is not None:   # unified: fold LoRA exactly into K/V
            k_off = tfm._bgmv(h, lora_l["a_k"], lora_l["b_k"],
                              lora_l["scaling"], adapter_ids)
            v_off = tfm._bgmv(h, lora_l["a_v"], lora_l["b_v"],
                              lora_l["scaling"], adapter_ids)
            k_off = k_off.reshape(bsz, s, cfg.num_kv_heads, hd)
            v_off = v_off.reshape(bsz, s, cfg.num_kv_heads, hd)
            if cfg.use_rope:
                from repro.core import rope as rope_lib
                k_off = rope_lib.apply_rope(k_off, sin, cos)
            k_base = k_base + k_off
            v_base = v_base + v_off
        return k_base, v_base, None, None, None, None

    def _pad_table(self, pages: Sequence[int], width: int,
                   dump: int) -> List[int]:
        """Crop/pad one block table to ``width`` entries."""
        bt = list(pages)[:width]
        return bt + [dump] * (width - len(bt))

    def _bucket_width(self, need: int) -> int:
        """Block-table width bucket for a batch needing ``need`` live
        pages: next power of two, floor ``min_table_pages``, capped at
        ``max_pages_per_req`` — shared by decode and prefill shapes."""
        return min(self.max_pages_per_req,
                   max(min(self.min_table_pages, self.max_pages_per_req),
                       _pow2(need)))

    def _maybe_quant(self, kb_, vb_):
        """Write-time bCache quantization (kv_quant == "int8"): the same
        per-(position, head) symmetric scheme as the dense-cache path
        (``tfm.quantize_kv``), so tier round trips stay bit-exact against
        what the kernels dequantize.  Returns (kb, vb, ks, vs) with
        ks/vs None on the full-precision path."""
        if not self.kv_quant:
            return kb_, vb_, None, None
        kq, ks = tfm.quantize_kv(kb_)
        vq, vs = tfm.quantize_kv(vb_)
        return kq, vq, ks, vs

    def _gather_kv(self, pools: Pools, li, bt_b, bt_r):
        """Legacy gather path: this layer's block-table pages as
        contiguous (B, W·page, ...) views — int8 pages dequantized with
        their scales (the kernels instead dequantize per page tile in
        VMEM), residuals only for disaggregated pools given ``bt_r``."""
        kc = pra.gather_base(pools.kb[li], bt_b)
        vc = pra.gather_base(pools.vb[li], bt_b)
        if self.kv_quant:
            dt = self.cfg.activation_dtype
            kc = (kc.astype(jnp.float32) * pra.gather_scale(
                pools.kb_s[li], bt_b)[..., None]).astype(dt)
            vc = (vc.astype(jnp.float32) * pra.gather_scale(
                pools.vb_s[li], bt_b)[..., None]).astype(dt)
        if not self.disagg or bt_r is None:
            return kc, vc, None, None
        r = self.cfg.lora.rank
        return (kc, vc, pra.gather_res(pools.kr[li], bt_r, r),
                pra.gather_res(pools.vr[li], bt_r, r))

    # ------------------------------------------------------------- decode
    def _decode_fn(self, params: Params, lora: Optional[Params],
                   pools: Pools, tokens, kv_len, adapter_ids, bt_b, bt_r,
                   wpage_b, wpage_r, woff, temps, top_ks, top_ps, seeds,
                   spos, poison, *, sampled):
        """One decode step for a padded batch.

        params/lora: the model weights and adapter stacks (arguments, so
        the compiled step is the same for any weights); pools: donated;
        tokens/kv_len/adapter_ids: (B,); bt_*: (B, W) block tables (W is
        the bucketed live width on the paged path, ``max_pages_per_req``
        on the gather path); wpage_*: (B,) page indices to write the new
        token's KV into (dump page for inactive rows); woff: (B,) in-page
        offsets; temps/top_ks/top_ps/seeds/spos: (B,) per-row sampling
        params (temp <= 0 -> greedy argmax, the seed's exact path);
        poison: (B,) fault-injection mask — rows > 0 get their logits
        forced to NaN in-jit (DESIGN.md §17), exercising the same
        quarantine path a real numeric blow-up takes; sampled: static —
        False compiles the argmax-only body.

        Returns ``(pools, next_tok, logits, row_ok)`` where ``row_ok`` is
        the per-row ``isfinite(logits).all()`` guard — it rides the
        step's existing single host sync, so quarantine detection costs
        zero extra syncs.
        """
        cfg = self.cfg
        bsz = tokens.shape[0]
        x = params["embed"][tokens][:, None]
        kmask_pos = None
        new_pools = pools
        for li in range(cfg.num_layers):
            p_l = self._layer(params["layers"], li)
            lora_l = self._layer(lora, li)
            h = base.rms_norm(x, p_l["ln1"], cfg.norm_eps)
            q, sin, cos = tfm._qkv(p_l, h, cfg, lora_l, adapter_ids,
                                   kv_len[:, None])
            kb_, vb_, kr_, vr_, bk, bv = self._project_kv(
                p_l, lora_l, h, sin, cos, adapter_ids)
            kb_, vb_, ks_, vs_ = self._maybe_quant(kb_, vb_)
            # write new token
            new_pools = write_tokens(
                new_pools, li, wpage_b, wpage_r, woff, kb_[:, 0], vb_[:, 0],
                None if ks_ is None else ks_[:, 0],
                None if vs_ is None else vs_[:, 0],
                kr_[:, 0] if self.disagg else None,
                vr_[:, 0] if self.disagg else None, cfg.lora.rank)
            kbp, vbp, krp, vrp, ksp, vsp = new_pools
            if self.use_paged:
                # page-native attention: pools + block tables, no gather
                attn = kernel_ops.paged_residual_attention(
                    q[:, 0], kbp, vbp,
                    krp if self.disagg else None,
                    vrp if self.disagg else None,
                    bk if self.disagg else None,
                    bv if self.disagg else None,
                    bt_b, bt_r if self.disagg else None, kv_len + 1,
                    scale=cfg.resolved_head_dim ** -0.5,
                    window=cfg.sliding_window,
                    rope_theta=cfg.rope_theta, use_rope=cfg.use_rope,
                    kb_scale=ksp, vb_scale=vsp, layer=li)
            else:
                # legacy: gather this request's pages -> contiguous view
                w = bt_b.shape[1] * self.page
                kc, vc, krc, vrc = self._gather_kv(new_pools, li, bt_b, bt_r)
                if self.disagg:
                    bk_rows = bk.reshape(bsz, cfg.lora.rank, -1)
                    bv_rows = bv.reshape(bsz, cfg.lora.rank, -1)
                else:
                    bk_rows = bv_rows = None
                if kmask_pos is None:
                    kmask_pos = jnp.broadcast_to(jnp.arange(w)[None],
                                                 (bsz, w))
                attn = tfm._attend(q, kc, vc, krc, vrc, bk_rows, bv_rows,
                                   kmask_pos, kv_len + 1, kv_len[:, None],
                                   cfg.sliding_window,
                                   cfg.resolved_head_dim ** -0.5, cfg,
                                   self.disagg)
            x = x + attn.reshape(bsz, 1, -1) @ p_l["wo"]
            h = base.rms_norm(x, p_l["ln2"], cfg.norm_eps)
            x = x + tfm.ffn(p_l, h, cfg)
        logits = tfm.unembed(params, x, cfg)[:, 0]
        logits = jnp.where(poison[:, None] > 0, jnp.nan, logits)
        row_ok = jnp.all(jnp.isfinite(logits), axis=-1)
        if sampled:
            next_tok = sample_tokens(logits, temps, top_ks, top_ps, seeds,
                                     spos)
        else:
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return new_pools, next_tok, logits, row_ok

    def decode(self, tokens, kv_len, adapter_ids, base_tables, res_tables,
               wpage_b, wpage_r, woff, temps=None, top_ks=None,
               top_ps=None, seeds=None, spos=None, poison=None):
        """One decode step over ``len(tokens)`` live rows.

        ``base_tables``/``res_tables`` are RAW per-request page lists; this
        method owns the shape policy: the batch pads to the next power of
        two (<= ``max_batch``) and, on the paged path, block tables
        crop/pad to the bucketed live width — so compile variants stay
        O(log max_batch · log max_pages_per_req) while per-step HBM
        traffic tracks actual ``kv_len``.  Returns DEVICE arrays
        ``(next_tok, logits, row_ok)``; rows past the live count are
        padding.
        """
        def prepare():
            bsz = len(tokens)
            assert bsz <= self.sc.max_batch, (bsz, self.sc.max_batch)
            bpad = min(_pow2(bsz), self.sc.max_batch)
            pages = [kvl // self.page + 1 for kvl in kv_len]
            width = self._table_width(pages)
            pad = bpad - bsz
            bt_b = [self._pad_table(p, width, self.dump_page)
                    for p in base_tables]
            bt_r = [self._pad_table(p, width, self.dump_page_r)
                    for p in res_tables]
            bt_b += [[self.dump_page] * width] * pad
            bt_r += [[self.dump_page_r] * width] * pad
            temps_ = _per_row(temps, 0.0, bsz, bpad)
            args = (
                _i32(_per_row(tokens, 0, bsz, bpad)),
                _i32(_per_row(kv_len, 0, bsz, bpad)),
                _i32(_per_row(adapter_ids, 0, bsz, bpad)),
                _i32(bt_b), _i32(bt_r),
                _i32(_per_row(wpage_b, self.dump_page, bsz, bpad)),
                _i32(_per_row(wpage_r, self.dump_page_r, bsz, bpad)),
                _i32(_per_row(woff, 0, bsz, bpad)),
                *self._sampling(temps_, top_ks, top_ps, seeds, spos,
                                poison, bsz, bpad))
            return (self._decode, args,
                    dict(sampled=any(t > 0 for t in temps_)),
                    dict(rows=bpad, qpad=1, width=width, live_tokens=bsz,
                         live_pages=sum(pages)))

        self.pools, next_tok, logits, row_ok = self._call("decode", prepare)
        return next_tok, logits, row_ok

    def _sampling(self, temps, top_ks, top_ps, seeds, spos, poison,
                  n: int, bpad: int):
        """The per-row sampling and fault-injection arguments of a call,
        padded to ``bpad`` rows with neutral values (``temps`` already
        padded)."""
        return (jnp.asarray(temps, jnp.float32),
                _i32(_per_row(top_ks, 0, n, bpad)),
                jnp.asarray(_per_row(top_ps, 1.0, n, bpad), jnp.float32),
                _i32(_per_row(seeds, 0, n, bpad)),
                _i32(_per_row(spos, 0, n, bpad)),
                _i32(_per_row(poison, 0, n, bpad)))

    def _call(self, path: str, prepare):
        """One executor call on ``path``, under its ``executor.<path>``
        span.  ``prepare()`` (``executor.prepare``) returns the jitted
        function, its device and static arguments, and the call's shape
        (``rows``, ``qpad``, ``width``, ``live_tokens``, ``live_pages``);
        the call runs under ``executor.dispatch``.  Both spans' host time,
        and the shape, count on ``path`` (``trace.ExecCounters``)."""
        with trace.span(f"executor.{path}") as sp:
            with self.counters.span(path, "prepare"):
                fn, args, static, shape = prepare()
            with self.counters.span(path, "dispatch"):
                out = fn(self.params, self.lora, self.pools, *args, **static)
            self.counters.count(path, **shape)
            sp.set_metadata(bpad=shape["rows"], qpad=shape["qpad"],
                            width=shape["width"])
        return out

    def decode_cache_size(self) -> int:
        """Number of compiled decode variants (bucket coverage probe)."""
        return self._decode._cache_size()

    # ------------------------------------------------------------ prefill
    def _prefill_fn(self, params: Params, lora: Optional[Params],
                    pools: Pools, tokens, start, n_valid, adapter_ids, bt_b,
                    bt_r, wpages_b, wpages_r, temps, top_ks, top_ps, seeds,
                    spos, poison, *, chunk, sampled, unified=False,
                    verify=False):
        """Chunked prefill for a PADDED BATCH of requests.

        params/lora/pools as :meth:`_decode_fn`; tokens: (B, chunk)
        padded; start: (B,) absolute position of each row's tokens[0]; n_valid: (B,) #real tokens per row (0 for padding
        rows); wpages_*: (B, chunk) page to write each token into (dump
        page where the cache is inherited — CoW: shared pages are never
        written); temps/top_ks/top_ps/seeds/spos: (B,) sampling params for
        each row's first generated token (sampled: static — False compiles
        the argmax-only body).

        ``unified`` (static) routes the paged attention through the mixed
        prefill/decode grid (DESIGN.md §14): same math, but each row's
        ``n_valid`` also rides into the kernel as its q-length so rows of
        wildly different lengths — decode rows padded to the chunk width
        next to full prefill chunks — share one launch with their padding
        rows masked to exact zeros.  The non-unified prefill grid instead
        leaves rows past ``n_valid`` as ignored garbage; both take their
        logits at row ``n_valid - 1``, so outputs agree.

        ``verify`` (static, DESIGN.md §16) additionally unembeds EVERY
        row position and reduces the longest greedy-accepted draft prefix
        in-jit: verify rows carry ``[t0, d_1..d_k]`` as their tokens, and
        draft ``d_{j+1}`` is accepted iff it equals the argmax after
        consuming ``[t0, d_1..d_j]`` AND every earlier draft was
        (cumprod over the match mask — no per-token host sync).  Returns
        the extended tuple ``(pools, next_tok, logits, greedy_all,
        n_acc)``; ``greedy_all[i, :n_acc[i]+1]`` is exactly the token
        run the engine commits (accepted drafts + the bonus correction
        token, whose input prefix is fully accepted so it is the true
        greedy continuation).

        ``poison``: (B,) fault-injection mask (rows > 0 → NaN logits);
        every return shape ends with ``row_ok``, the per-row isfinite
        guard on the final logits (DESIGN.md §17).
        """
        cfg = self.cfg
        bsz = tokens.shape[0]
        positions = start[:, None] + jnp.arange(chunk)[None]    # (B, chunk)
        x = params["embed"][tokens]                             # (B, chunk, d)
        woff = positions % self.page
        valid = jnp.arange(chunk)[None] < n_valid[:, None]      # (B, chunk)
        new_pools = pools
        for li in range(cfg.num_layers):
            p_l = self._layer(params["layers"], li)
            lora_l = self._layer(lora, li)
            h = base.rms_norm(x, p_l["ln1"], cfg.norm_eps)
            q, sin, cos = tfm._qkv(p_l, h, cfg, lora_l, adapter_ids,
                                   positions)
            kb_, vb_, kr_, vr_, bk, bv = self._project_kv(
                p_l, lora_l, h, sin, cos, adapter_ids)
            kb_, vb_, ks_, vs_ = self._maybe_quant(kb_, vb_)
            wp_b = jnp.where(valid, wpages_b, self.dump_page)
            wp_r = jnp.where(valid, wpages_r, self.dump_page_r)
            new_pools = write_tokens(new_pools, li, wp_b, wp_r, woff, kb_,
                                     vb_, ks_, vs_, kr_, vr_, cfg.lora.rank)
            kbp, vbp, krp, vrp, ksp, vsp = new_pools
            if self.use_paged and unified:
                # unified mixed grid (§14): per-row q-length scalar
                # prefetch — decode rows (n_valid=1) and prefill chunks
                # attend in ONE launch, padding rows exact-zeroed
                attn = kernel_ops.paged_residual_attention_mixed(
                    q, kbp, vbp,
                    krp if self.disagg else None,
                    vrp if self.disagg else None,
                    bk if self.disagg else None,
                    bv if self.disagg else None,
                    bt_b, bt_r if self.disagg else None, start, n_valid,
                    start + n_valid, scale=cfg.resolved_head_dim ** -0.5,
                    window=cfg.sliding_window, rope_theta=cfg.rope_theta,
                    use_rope=cfg.use_rope,
                    kb_scale=ksp, vb_scale=vsp, layer=li)
            elif self.use_paged:
                # page-native prefill (§13): the chunk's K/V is already in
                # the pools — stream KV page by page via the block tables,
                # causal mask inside the chunk, no gather-to-contiguous
                attn = kernel_ops.paged_residual_attention_prefill(
                    q, kbp, vbp,
                    krp if self.disagg else None,
                    vrp if self.disagg else None,
                    bk if self.disagg else None,
                    bv if self.disagg else None,
                    bt_b, bt_r if self.disagg else None, start,
                    start + n_valid, scale=cfg.resolved_head_dim ** -0.5,
                    window=cfg.sliding_window, rope_theta=cfg.rope_theta,
                    use_rope=cfg.use_rope,
                    kb_scale=ksp, vb_scale=vsp, layer=li)
            else:
                # legacy: gather every request's pages -> contiguous view
                w = bt_b.shape[1] * self.page
                kc, vc, krc, vrc = self._gather_kv(new_pools, li, bt_b, bt_r)
                if self.disagg:
                    bk_rows = bk.reshape(bsz, cfg.lora.rank, -1)
                    bv_rows = bv.reshape(bsz, cfg.lora.rank, -1)
                else:
                    bk_rows = bv_rows = None
                kmask_pos = jnp.broadcast_to(jnp.arange(w)[None], (bsz, w))
                attn = tfm._attend(q, kc, vc, krc, vrc, bk_rows, bv_rows,
                                   kmask_pos, start + n_valid, positions,
                                   cfg.sliding_window,
                                   cfg.resolved_head_dim ** -0.5, cfg,
                                   self.disagg)
            x = x + attn.reshape(bsz, chunk, -1) @ p_l["wo"]
            h = base.rms_norm(x, p_l["ln2"], cfg.norm_eps)
            x = x + tfm.ffn(p_l, h, cfg)
        # per-row logits of the LAST VALID token
        idx = jnp.maximum(n_valid - 1, 0).astype(jnp.int32)
        if verify:
            # unembed EVERY position once; the last-valid logits are a
            # gather from the same tensor (bit-identical to the x_last
            # path: unembed is a per-position matmul)
            logits_all = tfm.unembed(params, x, cfg)          # (B, chunk, V)
            greedy_all = jnp.argmax(logits_all, axis=-1).astype(jnp.int32)
            logits = jnp.take_along_axis(
                logits_all, idx[:, None, None], axis=1)[:, 0]
            # longest accepted draft prefix: token column j+1 must match
            # the greedy prediction at column j, for in-range drafts only
            ok = (tokens[:, 1:] == greedy_all[:, :-1]) & \
                (jnp.arange(1, chunk)[None] < n_valid[:, None])
            n_acc = jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(axis=1)
        else:
            x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
            logits = tfm.unembed(params, x_last, cfg)[:, 0]        # (B, V)
        logits = jnp.where(poison[:, None] > 0, jnp.nan, logits)
        row_ok = jnp.all(jnp.isfinite(logits), axis=-1)
        if sampled:
            next_tok = sample_tokens(logits, temps, top_ks, top_ps, seeds,
                                     spos)
        else:
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if verify:
            return new_pools, next_tok, logits, greedy_all, n_acc, row_ok
        return new_pools, next_tok, logits, row_ok

    def prefill_plan(self, n_rows: int):
        """Shape policy for a batched prefill of ``n_rows`` requests:
        returns ``(bpad, chunk)`` — the power-of-two padded batch and the
        per-row token budget (``max_prefill_tokens`` split across the
        PADDED batch, so compile variants stay logarithmic and B=1
        degenerates to the seed's single-request chunk).  The engine
        slices prompts with this BEFORE calling :meth:`prefill_batch`,
        which pads with the same plan."""
        bpad = _pow2(max(1, n_rows))
        return bpad, max(1, self.sc.max_prefill_tokens // bpad)

    def prefill_batch(self, chunks, starts, adapter_ids, base_tables,
                      res_tables, wpages_b, wpages_r, chunk_size,
                      temps=None, top_ks=None, top_ps=None, seeds=None,
                      spos=None, poison=None):
        """Batched chunked prefill: ``len(chunks)`` rows padded per
        :meth:`prefill_plan`, each row padded to ``chunk_size`` tokens.
        Block tables arrive as RAW page lists.  Returns DEVICE arrays
        ``(next_tok, logits, row_ok)`` — the engine syncs once per step,
        not per chunk.
        """
        def prepare():
            return self._prepare_rows(
                self.prefill_plan(len(chunks))[0], chunk_size, chunks,
                starts, adapter_ids, base_tables, res_tables, wpages_b,
                wpages_r, temps, top_ks, top_ps, seeds, spos, poison)

        self.pools, next_tok, logits, row_ok = self._call("prefill", prepare)
        return next_tok, logits, row_ok

    def _row_pages(self, chunks, starts) -> List[int]:
        """Pages each row reaches once its chunk is written."""
        return [-(-(s + len(c)) // self.page) for c, s in zip(chunks, starts)]

    def _table_width(self, pages: Sequence[int]) -> int:
        """Block-table width of a call whose rows reach ``pages``: the
        bucket of the largest on the paged path, every page of a request
        on the gather path."""
        if not self.use_paged:
            self.fallback_gather_calls += 1
            return self.max_pages_per_req
        return self._bucket_width(max(pages))

    def _prepare_rows(self, bpad: int, qpad: int, chunks, starts,
                      adapter_ids, base_tables, res_tables, wpages_b,
                      wpages_r, temps, top_ks, top_ps, seeds, spos, poison,
                      **static):
        """A prefill or mixed call for :meth:`_call`: ``len(chunks)`` rows,
        each padded to ``qpad`` tokens (pad columns write to the dump
        page) with block tables cropped/padded to the call's width, then
        padding rows to ``bpad`` (q_len 0, every write to the dump)."""
        n = len(chunks)
        # prefill width bucketing (§13): tables cover the batch's largest
        # post-chunk kv extent, bucketed like decode widths
        pages = self._row_pages(chunks, starts)
        w = self._table_width(pages)
        toks, nvalid, wb, wr, btb, btr = [], [], [], [], [], []
        for i in range(bpad):
            if i < n:
                row = list(chunks[i])
                pad = qpad - len(row)
                toks.append(row + [0] * pad)
                nvalid.append(len(row))
                wb.append(list(wpages_b[i]) + [self.dump_page] * pad)
                wr.append(list(wpages_r[i]) + [self.dump_page_r] * pad)
                btb.append(self._pad_table(base_tables[i], w,
                                           self.dump_page))
                btr.append(self._pad_table(res_tables[i], w,
                                           self.dump_page_r))
            else:
                toks.append([0] * qpad)
                nvalid.append(0)
                wb.append([self.dump_page] * qpad)
                wr.append([self.dump_page_r] * qpad)
                btb.append([self.dump_page] * w)
                btr.append([self.dump_page_r] * w)
        temps = _per_row(temps, 0.0, n, bpad)
        args = (_i32(toks), _i32(_per_row(starts, 0, n, bpad)),
                _i32(nvalid), _i32(_per_row(adapter_ids, 0, n, bpad)),
                _i32(btb), _i32(btr), _i32(wb), _i32(wr),
                *self._sampling(temps, top_ks, top_ps, seeds, spos, poison,
                                n, bpad))
        return (self._prefill, args,
                dict(chunk=qpad, sampled=any(t > 0 for t in temps),
                     **static),
                dict(rows=bpad, qpad=qpad, width=w,
                     live_tokens=sum(len(c) for c in chunks),
                     live_pages=sum(pages)))

    # ------------------------------------------------------- mixed batch
    def mixed_step(self, chunks, starts, adapter_ids, base_tables,
                   res_tables, wpages_b, wpages_r, temps=None, top_ks=None,
                   top_ps=None, seeds=None, spos=None, poison=None,
                   verify=False, qfloor=0):
        """One iteration-level mixed batch (DESIGN.md §14): decode rows
        (``chunks[i] == [last_token]``, ``starts[i] == kv_len``) and
        chunked-prefill rows side by side, executed as a SINGLE call.

        Shape policy: a plan whose rows are all single-token and fit the
        decode batch delegates to :meth:`decode` — steady-state decode
        keeps its own compiled variants (and the logarithmic
        variant-count bound probed by ``decode_cache_size``).  Truly
        mixed plans pad rows to the power-of-two chunk width of the
        LONGEST row and run the unified kernel grid, each row's real
        length riding in as its q-length.  Returns DEVICE arrays
        ``(next_tok, logits, row_ok)``; rows past ``len(chunks)`` are
        padding.

        ``verify=True`` (DESIGN.md §16): the plan carries speculative
        verify rows (``chunks[i] == [t0, d_1..d_k]``); returns the
        extended tuple ``(next_tok, logits, greedy_all, n_acc, row_ok)``
        with the per-position greedy tokens and accepted-prefix lengths.
        ``qfloor`` overrides the q-tile floor — verify-dominated plans
        with no prefill rows pad to pow2(k+1) instead of the 32-wide
        prefill tile, so a k=4 verify step is not 8x padding waste.
        """
        bsz = len(chunks)
        qmax = max(len(c) for c in chunks)
        if not verify and qmax == 1 and bsz <= self.sc.max_batch:
            # decode-shaped plan: write position == starts, attend over
            # starts+1 tokens — exactly the decode contract
            return self.decode(
                [c[0] for c in chunks], list(starts), adapter_ids,
                base_tables, res_tables,
                [w[0] for w in wpages_b], [w[0] for w in wpages_r],
                [s % self.page for s in starts], temps=temps,
                top_ks=top_ks, top_ps=top_ps, seeds=seeds, spos=spos,
                poison=poison)
        def prepare():
            # shape-bucket with FLOORS, not just pow2: which rows (and which
            # chunk lengths) coincide in a plan is timing-sensitive, so
            # bucketing purely by pow2(bsz)/pow2(qmax) sprays one compiled
            # variant per batch/chunk combination the schedule happens to
            # produce — and each stray compile is a multi-second stall in
            # the serving loop.  Flooring the batch at the steady-state size
            # and the q tile at the prefill chunk cap collapses both axes to
            # one or two stable buckets; pad rows/columns carry q_len 0 (or
            # sit past a row's q_len) and are skipped by the kernels'
            # live/mask conditions.
            qpad = _pow2(max(qmax, qfloor if qfloor > 0 else min(
                self.sc.max_prefill_tokens, 32)))
            bpad = _pow2(max(bsz, min(self.sc.max_batch, 4)))
            return self._prepare_rows(
                bpad, qpad, chunks, starts, adapter_ids, base_tables,
                res_tables, wpages_b, wpages_r, temps, top_ks, top_ps, seeds,
                spos, poison, unified=True, verify=verify)

        out = self._call("verify" if verify else "mixed", prepare)
        self.pools = out[0]
        return tuple(out[1:])

    # ------------------------------------------------- broadcast fork
    def _prefill_broadcast_fn(self, params: Params, lora: Params,
                              pools: Pools, tokens, start, n_valid,
                              adapter_ids, bt_b, wpages_b, wpages_r, *,
                              chunk, n_agents):
        """Beyond-paper broadcast fork (DESIGN.md §9): ONE base-trajectory
        pass over the shared context computes rCaches for ``n_agents``
        adapters at once (residuals are rank-r projections of the same x).

        tokens: (chunk,); adapter_ids: (n_agents,); wpages_r:
        (n_agents, chunk).  Base attention only (the approximation);
        bCache written once via wpages_b.
        """
        cfg = self.cfg
        positions = start + jnp.arange(chunk)
        x = params["embed"][tokens][None]
        woff = positions % self.page
        valid = jnp.arange(chunk) < n_valid
        new_pools = pools
        for li in range(cfg.num_layers):
            p_l = self._layer(params["layers"], li)
            lora_l = self._layer(lora, li)
            h = base.rms_norm(x, p_l["ln1"], cfg.norm_eps)
            # base trajectory: no q-LoRA
            q, sin, cos = tfm._qkv(p_l, h, cfg, None, None, positions[None])
            hd = cfg.resolved_head_dim
            kb_ = (h @ p_l["wk"]).reshape(1, chunk, cfg.num_kv_heads, hd)
            vb_ = (h @ p_l["wv"]).reshape(1, chunk, cfg.num_kv_heads, hd)
            if cfg.use_rope:
                from repro.core import rope as rope_lib
                kb_ = rope_lib.apply_rope(kb_, sin, cos)
            # all agents' residuals from the SAME x: (n_agents, chunk, r)
            a_k = lora_l["a_k"][adapter_ids]          # (K, d, r)
            a_v = lora_l["a_v"][adapter_ids]
            sc = lora_l["scaling"][adapter_ids].astype(x.dtype)
            kr_ = jnp.einsum("sd,kdr->ksr", h[0], a_k.astype(x.dtype)) \
                * sc[:, None, None]
            vr_ = jnp.einsum("sd,kdr->ksr", h[0], a_v.astype(x.dtype)) \
                * sc[:, None, None]
            kb_, vb_, ks_, vs_ = self._maybe_quant(kb_, vb_)
            wp_b = jnp.where(valid, wpages_b, self.dump_page)
            wp_r = jnp.where(valid[None], wpages_r, self.dump_page_r)
            new_pools = write_tokens(
                new_pools, li, wp_b, wp_r, woff[None], kb_[0], vb_[0],
                None if ks_ is None else ks_[0],
                None if vs_ is None else vs_[0], kr_, vr_, cfg.lora.rank)
            kbp, vbp, _, _, ksp, vsp = new_pools
            # attention over base cache only
            if self.use_paged:
                attn = kernel_ops.paged_residual_attention_prefill(
                    q, kbp, vbp, None, None, None, None,
                    bt_b[None], None, start[None],
                    (start + n_valid)[None],
                    scale=cfg.resolved_head_dim ** -0.5,
                    window=cfg.sliding_window, rope_theta=cfg.rope_theta,
                    use_rope=cfg.use_rope,
                    kb_scale=ksp, vb_scale=vsp, layer=li)
            else:
                w = bt_b.shape[0] * self.page
                kc, vc, _, _ = self._gather_kv(new_pools, li, bt_b[None],
                                               None)
                kmask_pos = jnp.arange(w)[None]
                attn = tfm._attend(q, kc, vc, None, None, None, None,
                                   kmask_pos, (start + n_valid)[None],
                                   positions[None], cfg.sliding_window,
                                   cfg.resolved_head_dim ** -0.5, cfg,
                                   False)
            x = x + attn.reshape(1, chunk, -1) @ p_l["wo"]
            h = base.rms_norm(x, p_l["ln2"], cfg.norm_eps)
            x = x + tfm.ffn(p_l, h, cfg)
        return new_pools

    def prefill_broadcast(self, tokens, start, adapter_ids, bt_b,
                          wpages_b, wpages_r_list, chunk_size):
        if not hasattr(self, "_broadcast_jit"):
            self._broadcast_jit = {}
        key = (chunk_size, len(adapter_ids))
        if key not in self._broadcast_jit:
            self._broadcast_jit[key] = jax.jit(
                self._prefill_broadcast_fn, donate_argnums=(2,),
                static_argnames=("chunk", "n_agents"))

        def prepare():
            n = len(tokens)
            pad = chunk_size - n
            pages = self._row_pages([tokens], [start])
            bt = self._pad_table(bt_b, self._table_width(pages),
                                 self.dump_page)
            args = (_i32(list(tokens) + [0] * pad), _i32(start), _i32(n),
                    _i32(list(adapter_ids)), _i32(bt),
                    _i32(list(wpages_b) + [self.dump_page] * pad),
                    _i32([list(w) + [self.dump_page_r] * pad
                          for w in wpages_r_list]))
            return (self._broadcast_jit[key], args,
                    dict(chunk=chunk_size, n_agents=len(adapter_ids)),
                    dict(rows=1, qpad=chunk_size, width=len(bt),
                         live_tokens=n, live_pages=pages[0]))

        self.pools = self._call("broadcast", prepare)
