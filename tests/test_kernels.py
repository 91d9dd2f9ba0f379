"""Pallas ResidualAttention kernels vs. the pure-jnp oracle.

Sweeps shapes, dtypes, GQA group sizes, ranks, windows and cache-length
padding; asserts allclose between the interpret-mode kernel and ref.py.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import rope as rope_lib
from repro.kernels import ref as ref_mod
from repro.kernels import residual_attention as ra


def make_inputs(key, *, bsz, sq, sk, hq, hkv, d, r, dtype, decode=False):
    ks = jax.random.split(key, 8)
    q = jax.random.normal(ks[0], (bsz, sq, hq, d), dtype)
    k_base = jax.random.normal(ks[1], (bsz, sk, hkv, d), dtype)
    v_base = jax.random.normal(ks[2], (bsz, sk, hkv, d), dtype)
    k_res = jax.random.normal(ks[3], (bsz, sk, r), dtype) * 0.3
    v_res = jax.random.normal(ks[4], (bsz, sk, r), dtype) * 0.3
    b_k = jax.random.normal(ks[5], (bsz, r, hkv * d), dtype) * 0.3
    b_v = jax.random.normal(ks[6], (bsz, r, hkv * d), dtype) * 0.3
    kpos = jnp.broadcast_to(jnp.arange(sk), (bsz, sk))
    sin, cos = rope_lib.rope_sincos(kpos, d)
    sin, cos = sin.astype(dtype), cos.astype(dtype)
    if decode:
        kv_len = jax.random.randint(ks[7], (bsz,), 1, sk + 1)
        qpos = (kv_len - 1)[:, None]
    else:
        kv_len = jnp.full((bsz,), sk, jnp.int32)
        qpos = jnp.broadcast_to(jnp.arange(sq), (bsz, sq))
    return q, k_base, v_base, k_res, v_res, b_k, b_v, sin, cos, qpos, kv_len


def tolerances(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bsz,sq,sk,hq,hkv,d,r", [
    (1, 128, 128, 4, 4, 64, 16),      # MHA
    (2, 64, 192, 8, 2, 64, 16),       # GQA group 4, sk not block-multiple
    (1, 100, 257, 6, 1, 128, 8),      # MQA, ragged shapes
    (2, 128, 128, 4, 4, 64, 32),      # larger rank
])
def test_prefill_matches_ref(dtype, bsz, sq, sk, hq, hkv, d, r):
    inp = make_inputs(jax.random.PRNGKey(0), bsz=bsz, sq=sq, sk=sk, hq=hq,
                      hkv=hkv, d=d, r=r, dtype=dtype)
    q, k_base, v_base, k_res, v_res, b_k, b_v, sin, cos, qpos, kv_len = inp
    scale = d ** -0.5
    got = ra.residual_attention_prefill(
        q, k_base, v_base, k_res, v_res, b_k, b_v, sin, cos, qpos, kv_len,
        scale=scale, block_q=64, block_k=64, interpret=True)
    want = ref_mod.residual_attention_ref(
        q, k_base, v_base, k_res, v_res, b_k, b_v, sin, cos,
        qpos=qpos, kv_len=kv_len, scale=scale)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               **tolerances(dtype))


@pytest.mark.parametrize("window", [0, 32])
def test_prefill_sliding_window(window):
    dtype = jnp.float32
    inp = make_inputs(jax.random.PRNGKey(1), bsz=1, sq=96, sk=96, hq=4,
                      hkv=2, d=64, r=16, dtype=dtype)
    q, k_base, v_base, k_res, v_res, b_k, b_v, sin, cos, qpos, kv_len = inp
    got = ra.residual_attention_prefill(
        q, k_base, v_base, k_res, v_res, b_k, b_v, sin, cos, qpos, kv_len,
        scale=0.125, window=window, block_q=32, block_k=32, interpret=True)
    want = ref_mod.residual_attention_ref(
        q, k_base, v_base, k_res, v_res, b_k, b_v, sin, cos,
        qpos=qpos, kv_len=kv_len, window=window, scale=0.125)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **tolerances(dtype))


def test_prefill_chunked_offset():
    """Chunked prefill: queries are a later chunk attending to a longer cache."""
    dtype = jnp.float32
    bsz, sq, sk, hq, hkv, d, r = 1, 64, 192, 4, 2, 64, 16
    inp = make_inputs(jax.random.PRNGKey(2), bsz=bsz, sq=sq, sk=sk, hq=hq,
                      hkv=hkv, d=d, r=r, dtype=dtype)
    q, k_base, v_base, k_res, v_res, b_k, b_v, sin, cos, _, _ = inp
    qpos = jnp.broadcast_to(jnp.arange(128, 128 + sq), (bsz, sq))
    kv_len = jnp.asarray([128 + sq], jnp.int32)
    got = ra.residual_attention_prefill(
        q, k_base, v_base, k_res, v_res, b_k, b_v, sin, cos, qpos, kv_len,
        scale=0.125, block_q=64, block_k=64, interpret=True)
    want = ref_mod.residual_attention_ref(
        q, k_base, v_base, k_res, v_res, b_k, b_v, sin, cos,
        qpos=qpos, kv_len=kv_len, scale=0.125)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **tolerances(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bsz,sk,hq,hkv,d,r,window", [
    (4, 256, 8, 2, 64, 16, 0),
    (2, 130, 4, 4, 128, 8, 0),
    (3, 256, 4, 1, 64, 32, 64),      # MQA + sliding window
])
def test_decode_matches_ref(dtype, bsz, sk, hq, hkv, d, r, window):
    inp = make_inputs(jax.random.PRNGKey(3), bsz=bsz, sq=1, sk=sk, hq=hq,
                      hkv=hkv, d=d, r=r, dtype=dtype, decode=True)
    q, k_base, v_base, k_res, v_res, b_k, b_v, sin, cos, qpos, kv_len = inp
    scale = d ** -0.5
    got = ra.residual_attention_decode(
        q[:, 0], k_base, v_base, k_res, v_res, b_k, b_v, sin, cos, kv_len,
        scale=scale, window=window, block_k=64, interpret=True)
    want = ref_mod.residual_attention_ref(
        q, k_base, v_base, k_res, v_res, b_k, b_v, sin, cos,
        qpos=qpos, kv_len=kv_len, window=window, scale=scale)[:, 0]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               **tolerances(dtype))


def test_zero_residual_reduces_to_plain_attention():
    """With zero rCache the kernel must equal vanilla attention on bCache."""
    from repro.core import attention as attn_lib
    dtype = jnp.float32
    inp = make_inputs(jax.random.PRNGKey(4), bsz=2, sq=64, sk=64, hq=4,
                      hkv=2, d=64, r=16, dtype=dtype)
    q, k_base, v_base, k_res, v_res, b_k, b_v, sin, cos, qpos, kv_len = inp
    z = jnp.zeros_like(k_res)
    got = ra.residual_attention_prefill(
        q, k_base, v_base, z, z, b_k, b_v, sin, cos, qpos, kv_len,
        scale=0.125, block_q=32, block_k=32, interpret=True)
    want = attn_lib.mha(q, k_base, v_base, causal=True, scale=0.125)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------
# RG-LRU linear-scan kernel (Griffin recurrence)
# --------------------------------------------------------------------------
def _lru_oracle(a, b, h0):
    bb = b.at[:, 0].add(a[:, 0] * h0)

    def op(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2

    _, states = jax.lax.associative_scan(op, (a, bb), axis=1)
    return states, states[:, -1]


@pytest.mark.parametrize("bsz,s,w,bs,bw,dtype", [
    (2, 128, 128, 64, 64, jnp.float32),
    (1, 200, 96, 64, 64, jnp.float32),      # ragged shapes (padding path)
    (2, 128, 128, 64, 64, jnp.bfloat16),
])
def test_rg_lru_matches_oracle(bsz, s, w, bs, bw, dtype):
    from repro.kernels.rg_lru import rg_lru_scan
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    a = jax.nn.sigmoid(jax.random.normal(k[0], (bsz, s, w))).astype(dtype)
    b = (jax.random.normal(k[1], (bsz, s, w)) * 0.2).astype(dtype)
    h0 = (jax.random.normal(k[2], (bsz, w)) * 0.5).astype(dtype)
    got, hlast = rg_lru_scan(a, b, h0, block_s=bs, block_w=bw,
                             interpret=True)
    want, wlast = _lru_oracle(a.astype(jnp.float32),
                              b.astype(jnp.float32),
                              h0.astype(jnp.float32))
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), **tol)
    np.testing.assert_allclose(np.asarray(hlast, np.float32),
                               np.asarray(wlast), **tol)


# --------------------------------------------------------------------------
# Paged ResidualAttention decode (block tables via scalar prefetch)
# --------------------------------------------------------------------------
def _base_pages(pool):
    """Storage (1, P, Hkv, page, D) -> page-major (P, page, Hkv, D)."""
    return jnp.swapaxes(pool[0], 1, 2)


def _res_pages(pool, r):
    """Packed (1, rows, page, G*r) residual pool -> page-major
    (rows*G, page, r): page id p lives in lanes (p % G)*r ... of row
    p // G."""
    pool = pool[0]
    rows, page, gr = pool.shape
    g = gr // r
    return pool.reshape(rows, page, g, r).transpose(0, 2, 1, 3).reshape(
        rows * g, page, r)


def make_paged_inputs(key, *, bsz, hq, hkv, d, r, page, npages, pool,
                      kv_len=None):
    """Random one-layer pools in the kernels' storage layouts (page-major
    draws converted by the layout helpers, with the leading layer axis)
    plus queries, B and block tables."""
    from repro.kernels import paged_residual_attention as pra
    ks = jax.random.split(key, 8)
    kb_pool = pra.to_base_pool(
        jax.random.normal(ks[0], (1, pool, page, hkv, d)))
    vb_pool = pra.to_base_pool(
        jax.random.normal(ks[1], (1, pool, page, hkv, d)))
    kr_pool = pra.to_res_pool(
        jax.random.normal(ks[2], (1, pool, page, r)) * 0.3)
    vr_pool = pra.to_res_pool(
        jax.random.normal(ks[3], (1, pool, page, r)) * 0.3)
    q = jax.random.normal(ks[4], (bsz, hq, d))
    b_k = jax.random.normal(ks[5], (bsz, r, hkv * d)) * 0.3
    b_v = jax.random.normal(ks[6], (bsz, r, hkv * d)) * 0.3
    perm = np.stack([np.random.default_rng(i).permutation(pool)[:npages]
                     for i in range(bsz)])
    bt = jnp.asarray(perm, jnp.int32)
    s = npages * page
    if kv_len is None:
        kv_len = [s] + [max(1, s // (i + 2)) for i in range(bsz - 1)]
    kv_len = jnp.asarray(kv_len, jnp.int32)
    return q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt, kv_len


def paged_dense_oracle(q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v,
                       bt, kv_len, *, use_rope=True):
    bsz, hq, d = q.shape
    hkv, page = kb_pool.shape[2], kb_pool.shape[3]
    s = bt.shape[1] * page
    r = b_k.shape[1]
    kb = _base_pages(kb_pool)[bt].reshape(bsz, s, hkv, d)
    vb = _base_pages(vb_pool)[bt].reshape(bsz, s, hkv, d)
    kr = _res_pages(kr_pool, r)[bt].reshape(bsz, s, r)
    vr = _res_pages(vr_pool, r)[bt].reshape(bsz, s, r)
    pos = jnp.broadcast_to(jnp.arange(s), (bsz, s))
    if use_rope:
        sin, cos = rope_lib.rope_sincos(pos, d)
    else:
        sin = jnp.zeros(pos.shape + (d // 2,), jnp.float32)
        cos = jnp.ones(pos.shape + (d // 2,), jnp.float32)
    return ref_mod.residual_attention_ref(
        q[:, None], kb, vb, kr, vr, b_k, b_v, sin, cos,
        qpos=(kv_len - 1)[:, None], kv_len=kv_len, scale=d ** -0.5)[:, 0]


@pytest.mark.parametrize("bsz,hq,hkv,d,r,page,npages,pool", [
    (3, 8, 2, 64, 16, 16, 8, 64),     # GQA group 4
    (2, 4, 4, 128, 8, 32, 4, 32),     # MHA, bigger pages, rank 8
    # blocked walk: 16 pages a step, a 32-page table is two blocks; rows
    # end at 512 / 256 (a block edge) / 170 tokens, so live blocks differ
    (3, 8, 2, 64, 16, 16, 32, 64),
    (2, 8, 1, 64, 8, 8, 64, 96),      # MQA, rank 8, 8-token pages: 2 x 32
    (2, 4, 4, 64, 64, 16, 16, 32),    # MHA (g = 1), rank 64, width == block
])
def test_paged_decode_matches_dense_oracle(bsz, hq, hkv, d, r, page,
                                           npages, pool):
    from repro.kernels.paged_residual_attention import (
        paged_residual_attention_decode)
    inp = make_paged_inputs(jax.random.PRNGKey(0), bsz=bsz, hq=hq, hkv=hkv,
                            d=d, r=r, page=page, npages=npages, pool=pool)
    q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt, kv_len = inp
    got = paged_residual_attention_decode(
        q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt, bt, kv_len,
        scale=d ** -0.5, interpret=True)
    want = paged_dense_oracle(q, kb_pool, vb_pool, kr_pool, vr_pool,
                              b_k, b_v, bt, kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bsz,hq,hkv,d,r,use_rope", [
    (2, 8, 1, 64, 16, True),          # MQA, group 8
    (2, 12, 4, 64, 8, True),          # GQA group 3, small rank
    (2, 8, 2, 64, 32, True),          # GQA group 4, large rank
    (2, 8, 2, 64, 16, False),         # RoPE disabled (whisper-style)
])
def test_paged_dispatcher_backends_agree(bsz, hq, hkv, d, r, use_rope):
    """ops.paged_residual_attention: the Pallas kernel (interpret) and the
    XLA gather mirror must agree — the serving executor swaps between them
    with one flag, so they must be interchangeable."""
    from repro.kernels import ops as kernel_ops
    page, npages, pool = 16, 4, 32
    inp = make_paged_inputs(jax.random.PRNGKey(1), bsz=bsz, hq=hq, hkv=hkv,
                            d=d, r=r, page=page, npages=npages, pool=pool)
    q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt, kv_len = inp
    kw = dict(scale=d ** -0.5, use_rope=use_rope)
    got = kernel_ops.paged_residual_attention(
        q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt, bt, kv_len,
        backend="pallas", interpret=True, **kw)
    want = kernel_ops.paged_residual_attention(
        q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt, bt, kv_len,
        backend="ref", **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    oracle = paged_dense_oracle(q, kb_pool, vb_pool, kr_pool, vr_pool,
                                b_k, b_v, bt, kv_len, use_rope=use_rope)
    np.testing.assert_allclose(np.asarray(got), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_base_only_variant():
    """Base-only kernel == disaggregated kernel with zero residuals ==
    ref backend with kr_pool=None (unified caches / no-LoRA requests)."""
    from repro.kernels import ops as kernel_ops
    from repro.kernels.paged_residual_attention import (
        paged_attention_decode_base, paged_residual_attention_decode)
    bsz, hq, hkv, d, r, page, npages, pool = 3, 8, 2, 64, 16, 16, 4, 32
    inp = make_paged_inputs(jax.random.PRNGKey(2), bsz=bsz, hq=hq, hkv=hkv,
                            d=d, r=r, page=page, npages=npages, pool=pool)
    q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt, kv_len = inp
    got = paged_attention_decode_base(q, kb_pool, vb_pool, bt, kv_len,
                                      scale=d ** -0.5, interpret=True)
    want_ref = kernel_ops.paged_residual_attention(
        q, kb_pool, vb_pool, None, None, None, None, bt, None, kv_len,
        backend="ref", scale=d ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_ref),
                               rtol=2e-5, atol=2e-5)
    z = jnp.zeros_like(kr_pool)
    want_zero = paged_residual_attention_decode(
        q, kb_pool, vb_pool, z, z, jnp.zeros_like(b_k), jnp.zeros_like(b_v),
        bt, bt, kv_len, scale=d ** -0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_zero),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_ragged_kv_len_page_skip():
    """Per-request page skipping: rows whose kv_len covers 1 page out of a
    wide table (dead blocks skipped, the last live block's tail masked)
    must still match the oracle exactly — including the kv_len=1
    degenerate row."""
    _check_ragged_decode(8, (1, 16, 19, 128))


@pytest.mark.parametrize("npages,kv_len", [
    # 24-page table: 12 pages (192 tokens) a block; rows end at 1, at the
    # block edge, 3 tokens into the second block, and at the table's end
    (24, (1, 192, 195, 384)),
    # 64-page table, four 16-page blocks: 1, 2, 3 and 4 live blocks
    (64, (200, 300, 700, 1024)),
])
def test_paged_decode_ragged_kv_len_page_skip_blocked(npages, kv_len):
    """The same across several blocks of the blocked walk."""
    _check_ragged_decode(npages, kv_len)


def _check_ragged_decode(npages, kv_len):
    from repro.kernels.paged_residual_attention import (
        paged_residual_attention_decode)
    bsz, hq, hkv, d, r, page, pool = 4, 4, 2, 64, 16, 16, 64
    inp = make_paged_inputs(jax.random.PRNGKey(3), bsz=bsz, hq=hq, hkv=hkv,
                            d=d, r=r, page=page, npages=npages, pool=pool,
                            kv_len=list(kv_len))
    q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt, kv_len = inp
    got = paged_residual_attention_decode(
        q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt, bt, kv_len,
        scale=d ** -0.5, interpret=True)
    want = paged_dense_oracle(q, kb_pool, vb_pool, kr_pool, vr_pool,
                              b_k, b_v, bt, kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------
# Paged chunked-prefill kernels (DESIGN.md §13): ragged chunk/window shapes
# --------------------------------------------------------------------------
def paged_prefill_dense_oracle(q, kb_pool, vb_pool, kr_pool, vr_pool, b_k,
                               b_v, bt, start, kv_len, *, window=0):
    """Independent oracle: gather pages -> contiguous views -> the dense
    residual_attention_ref with explicit qpos/kv_len/window masking."""
    bsz, sq, hq, d = q.shape
    hkv, page = kb_pool.shape[2], kb_pool.shape[3]
    s = bt.shape[1] * page
    r = b_k.shape[1]
    kb = _base_pages(kb_pool)[bt].reshape(bsz, s, hkv, d)
    vb = _base_pages(vb_pool)[bt].reshape(bsz, s, hkv, d)
    kr = _res_pages(kr_pool, r)[bt].reshape(bsz, s, r)
    vr = _res_pages(vr_pool, r)[bt].reshape(bsz, s, r)
    pos = jnp.broadcast_to(jnp.arange(s), (bsz, s))
    sin, cos = rope_lib.rope_sincos(pos, d)
    qpos = start[:, None] + jnp.arange(sq)[None]
    return ref_mod.residual_attention_ref(
        q, kb, vb, kr, vr, b_k, b_v, sin, cos, qpos=qpos, kv_len=kv_len,
        window=window, scale=d ** -0.5)


@pytest.mark.parametrize("sq,starts,window", [
    (27, (0, 5, 96), 0),       # chunk boundaries straddle pages, ragged
    (1, (0, 15, 63), 0),       # chunk == 1 degenerate case
    (16, (3, 48, 100), 5),     # window smaller than one page
    (24, (0, 20, 70), 24),     # window straddling a page boundary
])
def test_paged_prefill_matches_dense_oracle(sq, starts, window):
    """The chunked-prefill grid (running softmax across page steps, causal
    mask within the chunk, window-clamped page walk) must match the dense
    oracle for ragged starts/chunks — including rows mid-page."""
    from repro.kernels.paged_residual_attention import (
        paged_residual_attention_prefill)
    bsz, hq, hkv, d, r, page, npages, pool = len(starts), 8, 2, 64, 16, \
        16, 8, 64
    inp = make_paged_inputs(jax.random.PRNGKey(5), bsz=bsz, hq=hq, hkv=hkv,
                            d=d, r=r, page=page, npages=npages, pool=pool)
    _, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt, _ = inp
    q = jax.random.normal(jax.random.PRNGKey(6), (bsz, sq, hq, d))
    start = jnp.asarray(starts, jnp.int32)
    kv_len = start + sq
    got = paged_residual_attention_prefill(
        q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt, bt, start,
        kv_len, scale=d ** -0.5, window=window, interpret=True)
    want = paged_prefill_dense_oracle(q, kb_pool, vb_pool, kr_pool, vr_pool,
                                      b_k, b_v, bt, start, kv_len,
                                      window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [0, 24])
def test_paged_prefill_dispatcher_backends_agree(window):
    """ops.paged_residual_attention_prefill: the Pallas kernel (interpret)
    and the XLA gather mirror must be interchangeable — the serving
    executor swaps them with one flag."""
    from repro.kernels import ops as kernel_ops
    bsz, sq, hq, hkv, d, r, page, npages, pool = 2, 20, 4, 1, 64, 8, 16, \
        4, 32
    inp = make_paged_inputs(jax.random.PRNGKey(7), bsz=bsz, hq=hq, hkv=hkv,
                            d=d, r=r, page=page, npages=npages, pool=pool)
    _, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt, _ = inp
    q = jax.random.normal(jax.random.PRNGKey(8), (bsz, sq, hq, d))
    start = jnp.asarray([7, 30], jnp.int32)
    kv_len = start + sq
    kw = dict(scale=d ** -0.5, window=window)
    got = kernel_ops.paged_residual_attention_prefill(
        q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt, bt, start,
        kv_len, backend="pallas", interpret=True, **kw)
    want = kernel_ops.paged_residual_attention_prefill(
        q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt, bt, start,
        kv_len, backend="ref", **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_prefill_base_only_variant():
    """Base-only prefill kernel == disaggregated kernel with zero
    residuals == ref backend with kr_pool=None == the dense oracle with a
    zero residual stream (unified caches / base-only prefill)."""
    from repro.kernels import ops as kernel_ops
    from repro.kernels.paged_residual_attention import (
        paged_attention_prefill_base, paged_residual_attention_prefill)
    bsz, sq, hq, hkv, d, r, page, npages, pool = 2, 18, 8, 2, 64, 16, 16, \
        6, 48
    inp = make_paged_inputs(jax.random.PRNGKey(9), bsz=bsz, hq=hq, hkv=hkv,
                            d=d, r=r, page=page, npages=npages, pool=pool)
    _, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt, _ = inp
    q = jax.random.normal(jax.random.PRNGKey(10), (bsz, sq, hq, d))
    start = jnp.asarray([0, 41], jnp.int32)
    kv_len = start + sq
    got = paged_attention_prefill_base(q, kb_pool, vb_pool, bt, start,
                                       kv_len, scale=d ** -0.5,
                                       interpret=True)
    want_ref = kernel_ops.paged_residual_attention_prefill(
        q, kb_pool, vb_pool, None, None, None, None, bt, None, start,
        kv_len, backend="ref", scale=d ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_ref),
                               rtol=2e-5, atol=2e-5)
    z = jnp.zeros_like(kr_pool)
    want_zero = paged_residual_attention_prefill(
        q, kb_pool, vb_pool, z, z, jnp.zeros_like(b_k),
        jnp.zeros_like(b_v), bt, bt, start, kv_len, scale=d ** -0.5,
        interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_zero),
                               rtol=2e-5, atol=2e-5)
    want_oracle = paged_prefill_dense_oracle(
        q, kb_pool, vb_pool, z, z, jnp.zeros_like(b_k),
        jnp.zeros_like(b_v), bt, start, kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_oracle),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window,npages,kv_len", [
    pytest.param(5, 8, (3, 16, 77, 128), id="5"),
    pytest.param(32, 8, (3, 16, 77, 128), id="32"),
    # 48-page table, 16 pages a block: windows that start inside a block
    # (330 - 40 is page 18), or past a whole dead block (768 - 300)
    pytest.param(40, 48, (3, 16, 330, 768), id="40-wide"),
    pytest.param(300, 48, (3, 200, 330, 768), id="300-wide"),
])
def test_paged_decode_sliding_window_matches_ref(window, npages, kv_len):
    """SWA decode through the paged kernels (window-clamped page walk +
    in-block masking) vs the gather mirror, across ragged kv_len including
    windows smaller than one page and kv_len < window."""
    from repro.kernels import ops as kernel_ops
    bsz, hq, hkv, d, r, page, pool = 4, 8, 2, 64, 16, 16, 64
    inp = make_paged_inputs(jax.random.PRNGKey(11), bsz=bsz, hq=hq,
                            hkv=hkv, d=d, r=r, page=page, npages=npages,
                            pool=pool, kv_len=list(kv_len))
    q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt, kv_len = inp
    kw = dict(scale=d ** -0.5, window=window)
    got = kernel_ops.paged_residual_attention(
        q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt, bt, kv_len,
        backend="pallas", interpret=True, **kw)
    want = kernel_ops.paged_residual_attention(
        q, kb_pool, vb_pool, kr_pool, vr_pool, b_k, b_v, bt, bt, kv_len,
        backend="ref", **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # base-only variant under the same window
    got_b = kernel_ops.paged_residual_attention(
        q, kb_pool, vb_pool, None, None, None, None, bt, None, kv_len,
        backend="pallas", interpret=True, **kw)
    want_b = kernel_ops.paged_residual_attention(
        q, kb_pool, vb_pool, None, None, None, None, bt, None, kv_len,
        backend="ref", **kw)
    np.testing.assert_allclose(np.asarray(got_b), np.asarray(want_b),
                               rtol=2e-5, atol=2e-5)
