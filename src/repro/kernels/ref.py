"""Pure-jnp oracle for ResidualAttention (paper §5.3, Algorithm 1).

Computes attention over a *disaggregated* KV cache:

    K = K_base + RoPE(K_res @ B_k)
    V = V_base + V_res @ B_v
    O = softmax(Q K^T / sqrt(d)) V

The kernel implements this with on-chip reconstruction and a dual
accumulator; the oracle materializes everything, which is exactly the
"naive HBM reconstruction" the paper argues against — perfect as a
correctness reference.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core import attention as attn_lib
from repro.core import rope as rope_lib
from repro.kernels import paged_residual_attention as pra


def reconstruct(k_base, v_base, k_res, v_res, b_k, b_v, sin, cos):
    """Materialize full K, V from disaggregated parts.

    k_base/v_base: (B, Sk, Hkv, D); k_res/v_res: (B, Sk, R)
    b_k/b_v: (B, R, Hkv*D) per-request adapter up-projections
    sin/cos: (B, Sk, D//2)
    """
    bsz, sk, hkv, d = k_base.shape
    k_lora = jnp.einsum("bsr,brn->bsn", k_res.astype(jnp.float32),
                        b_k.astype(jnp.float32)).reshape(bsz, sk, hkv, d)
    k_lora = rope_lib.apply_rope(k_lora, sin, cos)
    v_lora = jnp.einsum("bsr,brn->bsn", v_res.astype(jnp.float32),
                        b_v.astype(jnp.float32)).reshape(bsz, sk, hkv, d)
    k = k_base.astype(jnp.float32) + k_lora
    v = v_base.astype(jnp.float32) + v_lora
    return k.astype(k_base.dtype), v.astype(v_base.dtype)


def _gather_paged_kv(q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v,
                     bt_b, bt_r, *, rope_theta: float, use_rope: bool,
                     kb_scale=None, vb_scale=None, layer: int = 0):
    """Gather block-table pages into contiguous (B, Sk, ...) views and, for
    the disaggregated layout, reconstruct full K/V.  Shared by the paged
    decode and prefill oracles.  Pools are in the kernels' storage layouts
    (``paged_residual_attention`` module docstring).  ``kb_scale``/
    ``vb_scale`` ((L, P, Hkv, 1, page) f32, or None) mark the base pools as
    int8: pages are dequantized right after the gather, BEFORE
    reconstruction, mirroring the kernels' in-VMEM dequant (DESIGN.md
    §18).  The (L, ...) pools are read at ``layer``."""
    bsz, d = q.shape[0], q.shape[-1]
    kb_pool, vb_pool, kb_scale, vb_scale = (
        None if x is None else x[layer]
        for x in (kb_pool, vb_pool, kb_scale, vb_scale))
    kb = pra.gather_base(kb_pool, bt_b)
    vb = pra.gather_base(vb_pool, bt_b)
    sk = kb.shape[1]
    if kb_scale is not None:
        ks = pra.gather_scale(kb_scale, bt_b)[..., None]
        vs = pra.gather_scale(vb_scale, bt_b)[..., None]
        kb = (kb.astype(jnp.float32) * ks).astype(q.dtype)
        vb = (vb.astype(jnp.float32) * vs).astype(q.dtype)
    if kr_pool is None:
        return kb, vb
    kr = pra.gather_res(kr_pool[layer], bt_r, b_k.shape[1])
    vr = pra.gather_res(vr_pool[layer], bt_r, b_k.shape[1])
    kpos = jnp.broadcast_to(jnp.arange(sk), (bsz, sk))
    if use_rope:
        sin, cos = rope_lib.rope_sincos(kpos, d, rope_theta)
    else:
        sin = jnp.zeros(kpos.shape + (d // 2,), jnp.float32)
        cos = jnp.ones(kpos.shape + (d // 2,), jnp.float32)
    return reconstruct(kb, vb, kr, vr, b_k, b_v,
                       sin.astype(q.dtype), cos.astype(q.dtype))


def _masked_softmax_attention(q, k, v, mask, scale):
    """Numerically-stable masked attention.  q: (B, Sq, Hq, D);
    k/v: (B, Sk, Hkv, D); mask: broadcastable to (B, Hq, Sq, Sk)."""
    s = attn_lib._gqa_scores(q, k) * scale
    s = jnp.where(mask, s, attn_lib.NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-20)
    return attn_lib._gqa_out(p, v).astype(q.dtype)


def paged_residual_attention_ref(q, kb_pool, vb_pool, kr_pool, vr_pool,
                                 b_k, b_v, bt_b, bt_r, kv_len, *,
                                 scale: Optional[float] = None,
                                 window: int = 0,
                                 rope_theta: float = 10_000.0,
                                 use_rope: bool = True,
                                 kb_scale=None,
                                 vb_scale=None,
                                 layer: int = 0) -> jnp.ndarray:
    """XLA mirror of the paged decode kernels: gather the block-table pages
    into contiguous views, then run the dense oracle.  Same interface as
    :func:`repro.kernels.paged_residual_attention.
    paged_residual_attention_decode` (pass ``kr_pool=None`` for the
    base-only variant), so the ``ops`` dispatcher can swap backends.

    The gather touches only ``bt_b.shape[1]`` pages per request — the
    serving executor crops/buckets block tables to the live page count, so
    even this fallback's HBM traffic scales with actual ``kv_len`` rather
    than the engine-wide ``smax`` (DESIGN.md §12).

    q: (B, Hq, D); kb/vb: (L, P, Hkv, page, D); kr/vr: packed residual
    pools (``paged_residual_attention`` module docstring) or None, read
    at ``layer``;
    b_k/b_v: (B, R, Hkv*D) or None; bt_b/bt_r: (B, W); kv_len: (B,) —
    the query row sits at position ``kv_len - 1``; ``window > 0`` keeps
    only the trailing ``window`` positions (SWA).  Returns (B, Hq, D).
    """
    bsz, hq, d = q.shape
    sk = bt_b.shape[1] * kb_pool.shape[-2]
    if scale is None:
        scale = d ** -0.5
    k, v = _gather_paged_kv(q, kb_pool, vb_pool, kr_pool, vr_pool, b_k,
                            b_v, bt_b, bt_r, rope_theta=rope_theta,
                            use_rope=use_rope, kb_scale=kb_scale,
                            vb_scale=vb_scale, layer=layer)
    kp = jnp.arange(sk)[None, None, None, :]
    # the query sits at kv_len - 1, so the causal bound and the validity
    # bound coincide: one mask term covers both
    kvl = kv_len[:, None, None, None]
    mask = kp < kvl
    if window > 0:
        mask = mask & (kp > kvl - 1 - window)
    return _masked_softmax_attention(q[:, None], k, v, mask, scale)[:, 0]


def paged_residual_attention_prefill_ref(q, kb_pool, vb_pool, kr_pool,
                                         vr_pool, b_k, b_v, bt_b, bt_r,
                                         start, kv_len, *,
                                         scale: Optional[float] = None,
                                         window: int = 0,
                                         rope_theta: float = 10_000.0,
                                         use_rope: bool = True,
                                         kb_scale=None, vb_scale=None,
                                         layer: int = 0) -> jnp.ndarray:
    """XLA mirror of the paged chunked-prefill kernels (DESIGN.md §13):
    gather block-table pages into contiguous views, reconstruct (disagg)
    and attend with the causal-within-chunk + window + validity mask.

    q: (B, chunk, Hq, D); start: (B,) absolute position of each chunk's
    first query row; kv_len: (B,) valid tokens incl. the chunk's writes.
    Pass ``kr_pool=None`` for the base-only variant.
    Returns (B, chunk, Hq, D).
    """
    bsz, sq, hq, d = q.shape
    sk = bt_b.shape[1] * kb_pool.shape[-2]
    if scale is None:
        scale = d ** -0.5
    k, v = _gather_paged_kv(q, kb_pool, vb_pool, kr_pool, vr_pool, b_k,
                            b_v, bt_b, bt_r, rope_theta=rope_theta,
                            use_rope=use_rope, kb_scale=kb_scale,
                            vb_scale=vb_scale, layer=layer)
    qpos = start[:, None] + jnp.arange(sq)[None]          # (B, Sq)
    qp = qpos[:, None, :, None]
    kp = jnp.arange(sk)[None, None, None, :]
    mask = (kp <= qp) & (kp < kv_len[:, None, None, None])
    if window > 0:
        mask = mask & (kp > qp - window)
    return _masked_softmax_attention(q, k, v, mask, scale)


def paged_residual_attention_mixed_ref(q, kb_pool, vb_pool, kr_pool,
                                       vr_pool, b_k, b_v, bt_b, bt_r,
                                       start, q_len, kv_len, *,
                                       scale: Optional[float] = None,
                                       window: int = 0,
                                       rope_theta: float = 10_000.0,
                                       use_rope: bool = True,
                                       kb_scale=None, vb_scale=None,
                                       layer: int = 0) -> jnp.ndarray:
    """XLA mirror of the unified mixed prefill/decode kernels
    (DESIGN.md §14): the prefill oracle generalized with a per-row
    ``q_len`` — rows past it are masked out AND explicitly zeroed in the
    output, matching the Pallas kernels' deterministic zero padding (a
    fully-masked softmax row would otherwise average V instead of
    vanishing).

    q: (B, chunk, Hq, D); start/q_len/kv_len: (B,) with
    ``kv_len = start + q_len``.  Pass ``kr_pool=None`` for the base-only
    variant.  Returns (B, chunk, Hq, D).
    """
    bsz, sq, hq, d = q.shape
    sk = bt_b.shape[1] * kb_pool.shape[-2]
    if scale is None:
        scale = d ** -0.5
    k, v = _gather_paged_kv(q, kb_pool, vb_pool, kr_pool, vr_pool, b_k,
                            b_v, bt_b, bt_r, rope_theta=rope_theta,
                            use_rope=use_rope, kb_scale=kb_scale,
                            vb_scale=vb_scale, layer=layer)
    rowidx = jnp.arange(sq)[None]                       # (1, Sq)
    rowvalid = rowidx < q_len[:, None]                  # (B, Sq)
    qpos = start[:, None] + rowidx
    qp = qpos[:, None, :, None]
    kp = jnp.arange(sk)[None, None, None, :]
    mask = (kp <= qp) & (kp < kv_len[:, None, None, None]) & \
        rowvalid[:, None, :, None]
    if window > 0:
        mask = mask & (kp > qp - window)
    out = _masked_softmax_attention(q, k, v, mask, scale)
    return jnp.where(rowvalid[:, :, None, None], out,
                     jnp.zeros_like(out))


def residual_attention_ref(q, k_base, v_base, k_res, v_res, b_k, b_v,
                           sin, cos, *, qpos: jnp.ndarray,
                           kv_len: Optional[jnp.ndarray] = None,
                           window: int = 0, causal: bool = True,
                           scale: Optional[float] = None) -> jnp.ndarray:
    """Reference residual attention.

    q: (B, Sq, Hq, D) — RoPE already applied (queries are computed fresh).
    qpos: (B, Sq) absolute positions of the query rows.
    kv_len: (B,) valid cache lengths (<= Sk).
    Returns (B, Sq, Hq, D).
    """
    k, v = reconstruct(k_base, v_base, k_res, v_res, b_k, b_v, sin, cos)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s = attn_lib._gqa_scores(q, k) * scale          # (B, Hq, Sq, Sk)
    kpos = jnp.arange(k.shape[1])[None, None, None, :]
    qp = qpos[:, None, :, None]
    mask = jnp.ones(s.shape, dtype=bool)
    if causal:
        mask &= kpos <= qp
    if window > 0:
        mask &= kpos > qp - window
    if kv_len is not None:
        mask &= kpos < kv_len[:, None, None, None]
    s = jnp.where(mask, s, attn_lib.NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return attn_lib._gqa_out(p, v).astype(q.dtype)
