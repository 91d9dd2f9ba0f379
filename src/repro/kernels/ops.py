"""Public entry points for ResidualAttention.

``residual_attention(...)`` and the paged dispatchers choose between the
Pallas kernels and the pure-jnp oracle in :mod:`repro.kernels.ref`.  On a
TPU the kernels are compiled by Mosaic (the paged grids are compile-tested
for a v5e in ``tests/test_tpu_compile.py``); elsewhere they run in
interpret mode.  The jitted model code calls these wrappers so the backend
can be swapped with one flag.
"""
from __future__ import annotations

import os
from typing import Optional

import jax.numpy as jnp

from repro.kernels import paged_residual_attention as pra
from repro.kernels import ref as ref_mod
from repro.kernels import residual_attention as ra

# Backend selection: "pallas" (interpret on CPU, compiled on TPU) or "ref".
# Unset -> platform-aware: the Pallas kernels on real TPU (the production
# hot path, DESIGN.md §12), the XLA ref mirror everywhere else (identical
# numerics, no per-grid-step interpret overhead on CPU).
# ``FORKKV_KERNEL_BACKEND`` is the CI-facing alias; its extra value
# "pallas-interpret" forces the Pallas kernels in interpret mode even off
# TPU (the backend-matrix CI job runs the parity suite under it).
_FORCE_INTERPRET = False


def _normalize(name: str) -> str:
    global _FORCE_INTERPRET
    _FORCE_INTERPRET = name == "pallas-interpret"
    return "pallas" if _FORCE_INTERPRET else name


_BACKEND = _normalize(os.environ.get("REPRO_ATTN_BACKEND", "")
                      or os.environ.get("FORKKV_KERNEL_BACKEND", ""))


def set_backend(name: str) -> None:
    global _BACKEND
    assert name in ("pallas", "pallas-interpret", "ref"), name
    _BACKEND = _normalize(name)


def get_backend() -> str:
    if _BACKEND:
        return _BACKEND
    import jax
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def interpret_mode() -> bool:
    """Whether the Pallas kernels run in interpret mode: off a TPU, or
    when ``pallas-interpret`` forces it."""
    if _FORCE_INTERPRET:
        return True
    import jax
    return jax.default_backend() != "tpu"


def _resolve_interpret(interpret):
    return interpret_mode() if interpret is None else interpret


def residual_attention(q, k_base, v_base, k_res, v_res, b_k, b_v, sin, cos,
                       *, qpos, kv_len, window: int = 0, causal: bool = True,
                       scale: Optional[float] = None,
                       backend: Optional[str] = None,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """Attention over a disaggregated KV cache.  Shapes as in ref.py."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    be = backend or get_backend()
    if be == "ref":
        return ref_mod.residual_attention_ref(
            q, k_base, v_base, k_res, v_res, b_k, b_v, sin, cos,
            qpos=qpos, kv_len=kv_len, window=window, causal=causal,
            scale=scale)
    interpret = _resolve_interpret(interpret)
    if q.shape[1] == 1:   # decode fast path
        out = ra.residual_attention_decode(
            q[:, 0], k_base, v_base, k_res, v_res, b_k, b_v, sin, cos,
            kv_len, scale=scale, window=window, interpret=interpret)
        return out[:, None]
    return ra.residual_attention_prefill(
        q, k_base, v_base, k_res, v_res, b_k, b_v, sin, cos, qpos, kv_len,
        scale=scale, causal=causal, window=window, interpret=interpret)


def paged_residual_attention(q, kb_pool, vb_pool, kr_pool, vr_pool, b_k,
                             b_v, bt_b, bt_r, kv_len, *,
                             scale: Optional[float] = None,
                             window: int = 0,
                             rope_theta: float = 10_000.0,
                             use_rope: bool = True,
                             kb_scale=None, vb_scale=None, layer: int = 0,
                             backend: Optional[str] = None,
                             interpret: Optional[bool] = None) -> jnp.ndarray:
    """Decode attention over paged pools + block tables (DESIGN.md §12).

    The serving hot path: the executor hands the pools and per-request
    block tables straight in — no gather-to-contiguous staging.  Dispatches
    like :func:`residual_attention`:

    * ``pallas`` — the paged kernels with scalar-prefetch block tables,
      per-request page skipping and (disaggregated variant) in-kernel
      deferred RoPE.  Compiled on TPU; ``interpret=True`` runs the same
      kernel code on CPU.
    * ``ref`` — the XLA gather mirror (:func:`repro.kernels.ref.
      paged_residual_attention_ref`); identical numerics-by-construction,
      runs anywhere, and still only touches ``bt_b.shape[1]`` pages.

    Pass ``kr_pool=None`` (with ``vr_pool``/``b_k``/``b_v``/``bt_r`` also
    None) for the base-only variant — unified caches or no-LoRA requests.
    Pools are stacked over layers, (L, ...), and read at the static
    ``layer``.
    ``kv_len`` counts ALL valid tokens incl. the one just written; the
    query row sits at position ``kv_len - 1``.  ``window > 0`` restricts
    attention to the trailing ``window`` positions (SWA) and skips the
    DMAs of out-of-window pages (DESIGN.md §13).  Returns (B, Hq, D).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    be = backend or get_backend()
    if be == "ref":
        return ref_mod.paged_residual_attention_ref(
            q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt_b, bt_r,
            kv_len, scale=scale, window=window, rope_theta=rope_theta,
            use_rope=use_rope, kb_scale=kb_scale, vb_scale=vb_scale,
            layer=layer)
    interpret = _resolve_interpret(interpret)
    if kr_pool is None:
        return pra.paged_attention_decode_base(
            q, kb_pool, vb_pool, bt_b, kv_len, scale=scale, window=window,
            kb_scale=kb_scale, vb_scale=vb_scale, layer=layer,
            interpret=interpret)
    return pra.paged_residual_attention_decode(
        q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt_b, bt_r,
        kv_len, scale=scale, window=window, rope_theta=rope_theta,
        use_rope=use_rope, kb_scale=kb_scale, vb_scale=vb_scale,
        layer=layer, interpret=interpret)


def paged_residual_attention_prefill(q, kb_pool, vb_pool, kr_pool, vr_pool,
                                     b_k, b_v, bt_b, bt_r, start, kv_len, *,
                                     scale: Optional[float] = None,
                                     window: int = 0,
                                     rope_theta: float = 10_000.0,
                                     use_rope: bool = True,
                                     kb_scale=None, vb_scale=None,
                                     layer: int = 0,
                                     backend: Optional[str] = None,
                                     interpret: Optional[bool] = None
                                     ) -> jnp.ndarray:
    """Chunked-prefill attention over paged pools + block tables
    (DESIGN.md §13) — the page-native half of the prefill hot path.

    q is a (B, chunk, Hq, D) tile whose K/V the executor has ALREADY
    written into the pools; KV streams page by page from base+residual
    pools via the block tables with a causal mask inside the chunk and a
    running softmax across page steps.  ``start`` (B,) is the absolute
    position of each chunk's first query row; ``kv_len`` (B,) counts valid
    tokens including the chunk's writes.  Backends exactly as
    :func:`paged_residual_attention`; pass ``kr_pool=None`` for the
    base-only variant.  Returns (B, chunk, Hq, D).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    be = backend or get_backend()
    if be == "ref":
        return ref_mod.paged_residual_attention_prefill_ref(
            q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt_b, bt_r,
            start, kv_len, scale=scale, window=window,
            rope_theta=rope_theta, use_rope=use_rope, kb_scale=kb_scale,
            vb_scale=vb_scale, layer=layer)
    interpret = _resolve_interpret(interpret)
    if kr_pool is None:
        return pra.paged_attention_prefill_base(
            q, kb_pool, vb_pool, bt_b, start, kv_len, scale=scale,
            window=window, kb_scale=kb_scale, vb_scale=vb_scale,
            layer=layer, interpret=interpret)
    return pra.paged_residual_attention_prefill(
        q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt_b, bt_r,
        start, kv_len, scale=scale, window=window, rope_theta=rope_theta,
        use_rope=use_rope, kb_scale=kb_scale, vb_scale=vb_scale,
        layer=layer, interpret=interpret)


def paged_residual_attention_mixed(q, kb_pool, vb_pool, kr_pool, vr_pool,
                                   b_k, b_v, bt_b, bt_r, start, q_len,
                                   kv_len, *, scale: Optional[float] = None,
                                   window: int = 0,
                                   rope_theta: float = 10_000.0,
                                   use_rope: bool = True,
                                   kb_scale=None, vb_scale=None,
                                   layer: int = 0,
                                   backend: Optional[str] = None,
                                   interpret: Optional[bool] = None
                                   ) -> jnp.ndarray:
    """Unified mixed prefill/decode attention (DESIGN.md §14): one launch
    over rows of different q-lengths — decode rows (``q_len=1``) and
    chunked-prefill rows (``q_len=chunk``) in the same batch, each row's
    q-length a scalar-prefetch operand.  Rows past ``q_len`` come back as
    exact zeros on EVERY backend.  ``kv_len`` must equal
    ``start + q_len`` per row.  Backends exactly as
    :func:`paged_residual_attention`; pass ``kr_pool=None`` for the
    base-only variant.  Returns (B, chunk, Hq, D).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    be = backend or get_backend()
    if be == "ref":
        return ref_mod.paged_residual_attention_mixed_ref(
            q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt_b, bt_r,
            start, q_len, kv_len, scale=scale, window=window,
            rope_theta=rope_theta, use_rope=use_rope, kb_scale=kb_scale,
            vb_scale=vb_scale, layer=layer)
    interpret = _resolve_interpret(interpret)
    if kr_pool is None:
        return pra.paged_attention_mixed_base(
            q, kb_pool, vb_pool, bt_b, start, q_len, kv_len, scale=scale,
            window=window, kb_scale=kb_scale, vb_scale=vb_scale,
            layer=layer, interpret=interpret)
    return pra.paged_residual_attention_mixed(
        q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt_b, bt_r,
        start, q_len, kv_len, scale=scale, window=window,
        rope_theta=rope_theta, use_rope=use_rope, kb_scale=kb_scale,
        vb_scale=vb_scale, layer=layer, interpret=interpret)
