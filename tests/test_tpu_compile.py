"""Mosaic compiles of the paged grids for a described TPU v5e.

Interpret mode accepts kernels that the TPU compiler refuses (block shapes
off the (8, 128) tiling, float iotas, unsupported shape casts), so these
tests compile every paged grid the serving path runs, at Llama3-8B
attention widths (32 query / 8 KV heads, head_dim 128, LoRA rank 16,
16-token pages, bf16), for a v5e chip that is described and not attached.
Nothing runs: a pass means the chip's compiler accepts the program, not
that it is fast or right.  The topology is described inside a fixture, so
importing this module never loads the TPU library.

The file also holds two CPU checks of the chip path: a lowered executor
step takes its weights as arguments (a step program must not carry the
model inside it), and the entry points' compile-cache helper sets no path
of its own when ``$JAX_COMPILATION_CACHE_DIR`` names one.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import paged_residual_attention as pra

HQ, HKV, D, R, PAGE = 32, 8, 128, 16, 16
LAYERS, POOL_PAGES, WIDTH = 2, 64, 16
SCALE = D ** -0.5


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _args(sharding, *, bsz, sq, int8, base_only, hq=HQ, width=WIDTH):
    """ShapeDtypeStructs for one grid call on the last layer of stacked
    pools, as the executor makes it, in the dispatcher's order."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    dt = jnp.bfloat16
    bdt = jnp.int8 if int8 else dt
    q = sds((bsz, hq, D) if sq is None else (bsz, sq, hq, D), dt)
    kb = sds((LAYERS, POOL_PAGES, HKV, PAGE, D), bdt)
    rows = pra.res_pool_rows(POOL_PAGES * HKV * D // R, R)
    kr = sds((LAYERS, rows, PAGE, pra.res_group(R) * R), dt)
    bk = sds((bsz, R, HKV * D), dt)
    bt = sds((bsz, width), jnp.int32)
    vec = sds((bsz,), jnp.int32)
    kw = dict(scale=SCALE, layer=LAYERS - 1, interpret=False)
    if int8:
        ks = sds((LAYERS, POOL_PAGES, HKV, 1, PAGE), jnp.float32)
        kw.update(kb_scale=ks, vb_scale=ks)
    if base_only:
        return (q, kb, kb, bt), vec, kw
    kw.update(rope_theta=500_000.0)
    return (q, kb, kb, kr, kr, bk, bk, bt, bt), vec, kw


GRIDS = {
    # name: (kernel, q rows per request or None for decode, batch,
    #        base-only, extra (B,) operands: start / q_len / kv_len)
    "decode": (pra.paged_residual_attention_decode, None, 8, False, 1),
    "decode_base": (pra.paged_attention_decode_base, None, 8, True, 1),
    "prefill": (pra.paged_residual_attention_prefill, 128, 2, False, 2),
    "prefill_base": (pra.paged_attention_prefill_base, 128, 2, True, 2),
    "mixed": (pra.paged_residual_attention_mixed, 128, 4, False, 3),
    "mixed_base": (pra.paged_attention_mixed_base, 128, 4, True, 3),
}


@pytest.mark.parametrize("name,int8", [
    ("decode", False), ("decode_base", False), ("prefill", False),
    ("prefill_base", False), ("mixed", False), ("mixed_base", False),
    ("decode_base", True), ("mixed_base", True), ("decode", True),
    ("mixed", True)])
def test_paged_grid_compiles_for_v5e(one_chip, name, int8):
    fn, sq, bsz, base_only, n_vec = GRIDS[name]
    args, vec, kw = _args(one_chip, bsz=bsz, sq=sq, int8=int8,
                          base_only=base_only)
    compiled = fn.lower(*args, *([vec] * n_vec), **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", ["decode", "mixed"])
def test_blocked_grids_compile_at_cell_shapes(one_chip, name):
    """The blocked walk at the doc-loop cell's shapes (InternLM2-1.8B's 16
    query / 8 KV heads, 16 rows, a 256-page table, a 256-token query tile
    for the mixed grid): 16 pages a step, all heads per decode step, one
    head per mixed step.  VMEM use only shows at this size."""
    fn, sq, _, base_only, n_vec = GRIDS[name]
    sq = None if sq is None else 256
    args, vec, kw = _args(one_chip, bsz=16, sq=sq, int8=False,
                          base_only=base_only, hq=16, width=256)
    compiled = fn.lower(*args, *([vec] * n_vec), **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _lowered_decode(ex, bsz=2):
    """Lower one decode step of ``ex`` for a ``bsz``-row batch."""
    w = ex.min_table_pages
    i32 = functools.partial(jnp.zeros, dtype=jnp.int32)
    f32 = functools.partial(jnp.ones, dtype=jnp.float32)
    return ex._decode.lower(
        ex.params, ex.lora, ex.pools, i32(bsz), i32(bsz), i32(bsz),
        i32((bsz, w)), i32((bsz, w)), i32(bsz), i32(bsz), i32(bsz),
        f32(bsz), i32(bsz), f32(bsz), i32(bsz), i32(bsz), i32(bsz),
        sampled=False)


def test_executor_step_takes_weights_as_arguments():
    """A lowered decode step is the same program for any weights, and is
    far smaller than them: the parameters are operands of the step, not
    constants folded into it."""
    from repro.configs.paper_models import tiny_serving_model
    from repro.core.config import ServeConfig
    from repro.models import transformer as tfm
    from repro.serving.executor import PagedExecutor

    cfg = tiny_serving_model(rank=8)
    sc = ServeConfig(page_size=16, max_pages=16, max_batch=2,
                     max_prefill_tokens=32, max_pages_per_req=4)
    texts = []
    for seed in (0, 1):
        params = tfm.init_params(cfg, jax.random.PRNGKey(seed))
        lora = tfm.init_lora_stacks(cfg, jax.random.PRNGKey(seed + 7),
                                    n_adapters=2)
        ex = PagedExecutor(cfg, params, lora, sc, disagg=True,
                           max_pages_per_req=4)
        texts.append(_lowered_decode(ex).as_text())
    weight_bytes = sum(t.nbytes for t in
                       jax.tree_util.tree_leaves((params, lora)))
    assert texts[0] == texts[1]
    assert len(texts[0]) < weight_bytes / 4, (len(texts[0]), weight_bytes)


def test_compile_cache_keeps_the_given_directory(monkeypatch, tmp_path):
    from repro.launch.compile_cache import use_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
