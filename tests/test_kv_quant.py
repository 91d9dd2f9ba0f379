"""int8 bCache paging inside the kernels (DESIGN.md §18).

Three layers of gates:

  * cross-backend parity — the Pallas kernels (interpret mode) and the
    XLA ref mirror dequantize the SAME int8 pages, so their outputs must
    agree to float32 accumulation noise (tight atol), for decode,
    chunked prefill and the unified mixed grid, disaggregated and
    base-only;
  * quality bound — int8 per-(position, head) symmetric quantization is
    lossy; the documented tolerance is a 5% max-abs error against the
    full-precision output (quantization error per element is <= scale/2
    ~ 0.4% of the per-token amax; softmax mixing keeps the output error
    well under the bound in practice);
  * serving parity — a greedy engine run with ``kv_quant="int8"``
    produces identical tokens on the paged path and the legacy gather
    path (both read the same quantized pools) with
    ``fallback_gather_calls == 0`` on the paged side.

The suite runs under whichever backend ``FORKKV_KERNEL_BACKEND``
selects, like tests/test_parity_matrix.py; the kernel-level tests pin
both backends explicitly.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_models import tiny_serving_model
from repro.core.config import ServeConfig
from repro.kernels import ops as kernel_ops
from repro.kernels import paged_residual_attention as pra
from repro.models import transformer as tfm
from repro.serving.api import ForkServer
from repro.serving.sampling import SamplingParams

PAGE = 16
P = 8          # pool pages
HKV = 2
HQ = 4
D = 64
R = 4
W = 3          # block-table width
WIDE = 32      # a wide table: two 16-page blocks of the blocked walk
ATOL_BACKEND = 1e-3   # same int8 pages, fp32 math: accumulation noise only
QUALITY_TOL = 0.05    # documented int8-vs-fp32 max-abs bound (DESIGN.md §18)


def _quant_pools(rng, pages=P):
    """Full-precision one-layer pools + their int8 quantization (+
    residuals), drawn page-major and stored in the kernels' pool layouts
    with the leading layer axis."""
    kb = jnp.asarray(rng.standard_normal((1, pages, PAGE, HKV, D)),
                     jnp.float32)
    vb = jnp.asarray(rng.standard_normal((1, pages, PAGE, HKV, D)),
                     jnp.float32)
    kq, ks = tfm.quantize_kv(kb)
    vq, vs = tfm.quantize_kv(vb)
    kr = jnp.asarray(rng.standard_normal((1, pages, PAGE, R)), jnp.float32)
    vr = jnp.asarray(rng.standard_normal((1, pages, PAGE, R)), jnp.float32)
    base, scale = pra.to_base_pool, pra.to_scale_pool
    return (base(kb), base(vb), base(kq), scale(ks), base(vq), scale(vs),
            pra.to_res_pool(kr), pra.to_res_pool(vr))


def _tables(rng, bsz, width=W):
    pages = P if width == W else 2 * bsz * width
    bt = rng.permutation(pages - 1)[: bsz * width].reshape(bsz, width)
    return jnp.asarray(bt, jnp.int32)


# (disaggregated, table width); the wide cases walk two page blocks
WIDTHS = [pytest.param(True, W, id="disagg"),
          pytest.param(False, W, id="base-only"),
          pytest.param(True, WIDE, id="disagg-wide"),
          pytest.param(False, WIDE, id="base-only-wide")]


@pytest.mark.parametrize("disagg,width", WIDTHS)
def test_int8_decode_backend_parity_and_quality(disagg, width):
    rng = np.random.default_rng(0)
    bsz = 2
    kb, vb, kq, ks, vq, vs, kr, vr = _quant_pools(
        rng, P if width == W else 2 * bsz * width)
    q = jnp.asarray(rng.standard_normal((bsz, HQ, D)), jnp.float32)
    bt_b = _tables(rng, bsz, width)
    bt_r = _tables(rng, bsz, width)
    kv_len = jnp.asarray([PAGE * width - 3, PAGE + 5], jnp.int32)
    if disagg:
        b_k = jnp.asarray(rng.standard_normal((bsz, R, HKV * D)) * 0.1,
                          jnp.float32)
        b_v = jnp.asarray(rng.standard_normal((bsz, R, HKV * D)) * 0.1,
                          jnp.float32)
        args = (q, kq, vq, kr, vr, b_k, b_v, bt_b, bt_r, kv_len)
        full = (q, kb, vb, kr, vr, b_k, b_v, bt_b, bt_r, kv_len)
    else:
        args = (q, kq, vq, None, None, None, None, bt_b, None, kv_len)
        full = (q, kb, vb, None, None, None, None, bt_b, None, kv_len)
    kw = dict(scale=D ** -0.5, kb_scale=ks, vb_scale=vs)
    o_ref = np.asarray(kernel_ops.paged_residual_attention(
        *args, backend="ref", **kw))
    o_pal = np.asarray(kernel_ops.paged_residual_attention(
        *args, backend="pallas", interpret=True, **kw))
    np.testing.assert_allclose(o_pal, o_ref, atol=ATOL_BACKEND,
                               rtol=ATOL_BACKEND)
    # quality: int8 vs full-precision within the documented bound
    o_fp = np.asarray(kernel_ops.paged_residual_attention(
        *full, backend="ref", scale=D ** -0.5))
    err = np.abs(o_ref - o_fp).max()
    assert err <= QUALITY_TOL * np.abs(o_fp).max(), err


@pytest.mark.parametrize("disagg", [True, False],
                         ids=["disagg", "base-only"])
def test_int8_prefill_backend_parity(disagg):
    rng = np.random.default_rng(1)
    kb, vb, kq, ks, vq, vs, kr, vr = _quant_pools(rng)
    bsz, chunk = 2, 8
    q = jnp.asarray(rng.standard_normal((bsz, chunk, HQ, D)), jnp.float32)
    bt_b = _tables(rng, bsz)
    bt_r = _tables(rng, bsz)
    start = jnp.asarray([PAGE, 4], jnp.int32)
    kv_len = start + chunk
    if disagg:
        b_k = jnp.asarray(rng.standard_normal((bsz, R, HKV * D)) * 0.1,
                          jnp.float32)
        b_v = jnp.asarray(rng.standard_normal((bsz, R, HKV * D)) * 0.1,
                          jnp.float32)
        args = (q, kq, vq, kr, vr, b_k, b_v, bt_b, bt_r, start, kv_len)
    else:
        args = (q, kq, vq, None, None, None, None, bt_b, None, start,
                kv_len)
    kw = dict(scale=D ** -0.5, kb_scale=ks, vb_scale=vs)
    o_ref = np.asarray(kernel_ops.paged_residual_attention_prefill(
        *args, backend="ref", **kw))
    o_pal = np.asarray(kernel_ops.paged_residual_attention_prefill(
        *args, backend="pallas", interpret=True, **kw))
    np.testing.assert_allclose(o_pal, o_ref, atol=ATOL_BACKEND,
                               rtol=ATOL_BACKEND)


@pytest.mark.parametrize("disagg,width", WIDTHS)
def test_int8_mixed_backend_parity(disagg, width):
    """Mixed grid: a decode row (q_len=1) and a prefill row (q_len=chunk)
    share one launch; padding rows are exact zeros on both backends."""
    rng = np.random.default_rng(2)
    bsz, chunk = 2, 8
    kb, vb, kq, ks, vq, vs, kr, vr = _quant_pools(
        rng, P if width == W else 2 * bsz * width)
    q = jnp.asarray(rng.standard_normal((bsz, chunk, HQ, D)), jnp.float32)
    bt_b = _tables(rng, bsz, width)
    bt_r = _tables(rng, bsz, width)
    start = jnp.asarray([PAGE * (1 if width == W else width - 1) + 7, 4],
                        jnp.int32)
    q_len = jnp.asarray([1, chunk], jnp.int32)
    kv_len = start + q_len
    if disagg:
        b_k = jnp.asarray(rng.standard_normal((bsz, R, HKV * D)) * 0.1,
                          jnp.float32)
        b_v = jnp.asarray(rng.standard_normal((bsz, R, HKV * D)) * 0.1,
                          jnp.float32)
        args = (q, kq, vq, kr, vr, b_k, b_v, bt_b, bt_r, start, q_len,
                kv_len)
    else:
        args = (q, kq, vq, None, None, None, None, bt_b, None, start,
                q_len, kv_len)
    kw = dict(scale=D ** -0.5, kb_scale=ks, vb_scale=vs)
    o_ref = np.asarray(kernel_ops.paged_residual_attention_mixed(
        *args, backend="ref", **kw))
    o_pal = np.asarray(kernel_ops.paged_residual_attention_mixed(
        *args, backend="pallas", interpret=True, **kw))
    np.testing.assert_allclose(o_pal, o_ref, atol=ATOL_BACKEND,
                               rtol=ATOL_BACKEND)
    # padding rows past q_len are exact zeros on both backends
    assert np.all(o_ref[0, 1:] == 0.0)
    assert np.all(o_pal[0, 1:] == 0.0)


# ---------------------------------------------------------------- serving
@pytest.fixture(scope="module")
def model_int8():
    cfg = dataclasses.replace(tiny_serving_model(rank=8), kv_quant="int8")
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    lora = tfm.init_lora_stacks(cfg, jax.random.PRNGKey(1), n_adapters=16)
    return cfg, params, lora


def _serve(model, mode, *, paged):
    cfg, params, lora = model
    sc = ServeConfig(page_size=16, max_pages=192, max_batch=4,
                     max_prefill_tokens=64, mode=mode,
                     max_pages_per_req=12, use_paged_kernel=paged)
    return ForkServer(cfg, params, lora, sc)


@pytest.mark.parametrize("mode", ["forkkv", "prefix"])
def test_int8_engine_paged_vs_gather_parity(model_int8, mode):
    """Greedy serving with int8 bCache pages: the paged kernels and the
    legacy gather path read the same quantized pools, so tokens must be
    IDENTICAL — and the paged side takes zero gather fallbacks."""
    cfg = model_int8[0]
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(0, cfg.vocab_size, 30 + 9 * i))
               for i in range(3)]
    outs = {}
    for paged in (True, False):
        server = _serve(model_int8, mode, paged=paged)
        hs = [server.generate(i + 1, p, SamplingParams(max_new_tokens=6))
              for i, p in enumerate(prompts)]
        outs[paged] = [o.tokens for o in server.wait(hs)]
        m = server.metrics()
        if paged:
            assert m["fallback_gather_calls"] == 0, m
        else:
            assert m["fallback_gather_calls"] > 0, m
    assert outs[True] == outs[False]


def test_int8_engine_fork_reuse(model_int8):
    """CoW forks over quantized shared pages still hit the radix cache:
    two agents forked off one shared context reuse its int8 pages."""
    cfg = model_int8[0]
    rng = np.random.default_rng(8)
    shared = list(rng.integers(0, cfg.vocab_size, 48))
    server = _serve(model_int8, "forkkv", paged=True)
    outs = []
    for i in range(2):       # sequential: the 2nd forks off the 1st's pages
        h = server.generate(i + 1, shared + list(
            rng.integers(0, cfg.vocab_size, 8)),
            SamplingParams(max_new_tokens=4))
        outs.append(server.wait([h])[0].tokens)
    assert all(len(t) == 4 for t in outs)
    assert server.metrics()["hit_tokens"] > 0
