"""Iteration-level scheduler invariants + unified-grid oracle checks
(DESIGN.md §14).

The planner is pure (no model, no device), so its contract is locked
down directly on :class:`IterationScheduler`:

  * the token budget is never exceeded (except by decode rows, which are
    NEVER starved no matter how small the budget),
  * decode rows come first and are capped at ``max_batch``,
  * prefill chunks fill FCFS, bounded by prompt remainder, remaining
    budget and ``max_prefill_tokens``,
  * ``first_scheduled_at`` is stamped exactly once.

Plus: a direct numerics check of the unified mixed kernels against their
ref oracle (per-row q-lengths, exact-zero padding rows), and an
engine-level check that stall detection still fires under the
mixed-batching default.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_models import tiny_serving_model
from repro.core.config import ServeConfig
from repro.models import transformer as tfm
from repro.serving.engine import Engine, Request
from repro.serving.scheduler import IterationScheduler


def mk_req(rid, state, prompt_len=100, pos=0, kv=0, out=0, max_new=8):
    r = Request(rid=rid, adapter_id=0, prompt=list(range(prompt_len)),
                max_new_tokens=max_new)
    r.state = state
    r.prefill_pos = pos
    r.kv_len = kv
    r.output = list(range(out))
    return r


# ---------------------------------------------------- planning invariants
def test_budget_never_exceeded_and_decode_priority():
    sc = ServeConfig(max_batch=4, max_prefill_tokens=32,
                     max_prefill_batch=8, iteration_token_budget=40)
    sched = IterationScheduler(sc)
    running = [mk_req(i, "decode", kv=50, out=2) for i in range(3)] + \
              [mk_req(10 + i, "prefill", prompt_len=200)
               for i in range(4)]
    plan = sched.plan(running)
    assert plan.total_tokens <= max(plan.budget, len(plan.decode_rows))
    assert plan.total_tokens <= 40
    # decode rows first, all of them, q=1 at the request's kv_len
    assert [rp.kind for rp in plan.rows[:3]] == ["decode"] * 3
    assert all(rp.q_len == 1 and rp.start == 50
               for rp in plan.decode_rows)
    assert all(rp.q_len <= sc.max_prefill_tokens
               for rp in plan.prefill_rows)


def test_decode_never_starved_by_tiny_budget():
    sc = ServeConfig(max_batch=8, iteration_token_budget=2)
    running = [mk_req(i, "decode", kv=50, out=1) for i in range(6)] + \
              [mk_req(10, "prefill", prompt_len=100)]
    plan = IterationScheduler(sc).plan(running)
    # every decode row runs even though the budget (2) can't cover them;
    # prefill gets nothing this iteration
    assert len(plan.decode_rows) == 6
    assert len(plan.prefill_rows) == 0


def test_decode_capped_at_max_batch_and_exhausted_rows_skipped():
    sc = ServeConfig(max_batch=2, iteration_token_budget=100)
    running = [mk_req(i, "decode", kv=50, out=1) for i in range(4)]
    running.append(mk_req(9, "decode", kv=50, out=9, max_new=8))
    plan = IterationScheduler(sc).plan(running)
    assert len(plan.decode_rows) == 2
    # a request that already has max_new+1 tokens is not schedulable
    assert all(rp.req.rid != 9 for rp in plan.rows)


def test_prefill_chunks_fcfs_with_prompt_and_budget_bounds():
    sc = ServeConfig(max_batch=4, max_prefill_tokens=16,
                     iteration_token_budget=24)
    running = [mk_req(1, "prefill", prompt_len=100, pos=90),  # 10 left
               mk_req(2, "prefill", prompt_len=100),
               mk_req(3, "prefill", prompt_len=100)]
    plan = IterationScheduler(sc).plan(running)
    q = {rp.req.rid: rp.q_len for rp in plan.prefill_rows}
    # final chunk: the exact 10-token remainder (tail pad paid once)
    assert q[1] == 10
    # mid-prompt chunks: budget remainder (24-10=14, then 24-18=6)
    # clamped DOWN to a power of two so the padded q tile stays tight
    assert q[2] == 8
    assert q[3] == 4
    assert plan.total_tokens == 22
    assert plan.rows[0].end == 100


def test_budget_exhaustion_stops_prefill_packing():
    sc = ServeConfig(max_batch=4, max_prefill_tokens=16,
                     iteration_token_budget=16)
    running = [mk_req(1, "prefill", prompt_len=16),
               mk_req(2, "prefill", prompt_len=100)]
    plan = IterationScheduler(sc).plan(running)
    q = {rp.req.rid: rp.q_len for rp in plan.prefill_rows}
    assert q == {1: 16}          # head takes the whole budget, FCFS
    assert plan.total_tokens == 16


def test_first_scheduled_stamped_once():
    sched = IterationScheduler(ServeConfig(iteration_token_budget=64))
    r = mk_req(1, "prefill", prompt_len=100)
    sched.plan([r], now=123.0)
    assert r.first_scheduled_at == 123.0
    sched.plan([r], now=456.0)
    assert r.first_scheduled_at == 123.0


def test_default_budget_covers_legacy_throughput():
    """budget=0 derives max_prefill_tokens + max_batch: a full decode
    batch ON TOP of the legacy prefill budget, so enabling mixed
    batching can never shrink per-step throughput."""
    sc = ServeConfig(max_batch=8, max_prefill_tokens=64)
    assert IterationScheduler(sc).budget == 64 + 8


def test_mixed_plan_flag():
    sched = IterationScheduler(ServeConfig(iteration_token_budget=64))
    both = sched.plan([mk_req(1, "decode", kv=10, out=1),
                       mk_req(2, "prefill", prompt_len=50)])
    assert both.is_mixed and both.q_max > 1
    assert not sched.plan([mk_req(1, "decode", kv=10, out=1)]).is_mixed


# ------------------------------------------- unified-grid kernel oracle
def _rand_mixed_inputs(key, *, window, wide=False):
    """Random one-layer pools (with the leading layer axis) + a 3-row
    batch mixing a decode row (q_len=1), a full prefill chunk and a
    q_len=0 padding row.  ``wide``: 64-page tables, two blocks of 32
    pages for the blocked walk, the rows live in different blocks."""
    page, hkv, g, d, r, npages = 8, 2, 2, 16, 4, 8
    if wide:
        npages = 96
    sq = 4
    hq = hkv * g
    from repro.kernels import paged_residual_attention as pra
    ks = jax.random.split(key, 8)
    kb = pra.to_base_pool(
        jax.random.normal(ks[0], (1, npages, page, hkv, d), jnp.float32))
    vb = pra.to_base_pool(
        jax.random.normal(ks[1], (1, npages, page, hkv, d), jnp.float32))
    kr = pra.to_res_pool(
        0.1 * jax.random.normal(ks[2], (1, npages, page, r), jnp.float32))
    vr = pra.to_res_pool(
        0.1 * jax.random.normal(ks[3], (1, npages, page, r), jnp.float32))
    q = jax.random.normal(ks[4], (3, sq, hq, d), jnp.float32)
    b_k = 0.1 * jax.random.normal(ks[5], (3, r, hkv * d), jnp.float32)
    b_v = 0.1 * jax.random.normal(ks[6], (3, r, hkv * d), jnp.float32)
    if wide:
        rng = np.random.default_rng(3)
        bt_b = jnp.asarray(rng.integers(0, npages, (3, 64)), jnp.int32)
        bt_r = jnp.asarray(rng.integers(0, npages, (3, 64)), jnp.int32)
        start = jnp.asarray([300, 250, 0], jnp.int32)
    else:
        bt_b = jnp.asarray([[0, 1, 2], [3, 4, 5], [0, 0, 0]], jnp.int32)
        bt_r = jnp.asarray([[5, 6, 7], [1, 2, 3], [0, 0, 0]], jnp.int32)
        start = jnp.asarray([17, 4, 0], jnp.int32)
    q_len = jnp.asarray([1, 4, 0], jnp.int32)       # decode | prefill | pad
    kv_len = start + q_len
    kw = dict(scale=d ** -0.5, window=window, rope_theta=10_000.0,
              use_rope=True)
    return (q, kb, vb, kr, vr, b_k, b_v, bt_b, bt_r, start, q_len,
            kv_len), kw, q_len


@pytest.mark.parametrize("window,wide", [
    pytest.param(0, False, id="0"), pytest.param(12, False, id="12"),
    pytest.param(0, True, id="0-wide"), pytest.param(20, True, id="20-wide")])
def test_mixed_kernel_matches_ref_oracle(window, wide):
    """The Pallas unified grid (interpret mode) must match the XLA mixed
    oracle row for row — including EXACT zeros past each row's q_len,
    the cross-backend determinism the prefill grid never promised."""
    from repro.kernels import paged_residual_attention as pra
    from repro.kernels import ref as ref_mod
    args, kw, q_len = _rand_mixed_inputs(jax.random.PRNGKey(0),
                                         window=window, wide=wide)
    got = pra.paged_residual_attention_mixed(*args, **kw, interpret=True)
    want = ref_mod.paged_residual_attention_mixed_ref(*args, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    for i, ql in enumerate(np.asarray(q_len)):
        np.testing.assert_array_equal(np.asarray(got)[i, ql:], 0.0)


@pytest.mark.parametrize("window", [0, 12])
def test_mixed_base_kernel_matches_ref_oracle(window):
    from repro.kernels import paged_residual_attention as pra
    from repro.kernels import ref as ref_mod
    args, kw, q_len = _rand_mixed_inputs(jax.random.PRNGKey(1),
                                         window=window)
    q, kb, vb = args[0], args[1], args[2]
    bt_b, start, q_len_, kv_len = args[7], args[9], args[10], args[11]
    base_kw = dict(scale=kw["scale"], window=window)
    got = pra.paged_attention_mixed_base(q, kb, vb, bt_b, start, q_len_,
                                         kv_len, **base_kw,
                                         interpret=True)
    want = ref_mod.paged_residual_attention_mixed_ref(
        q, kb, vb, None, None, None, None, bt_b, None, start, q_len_,
        kv_len, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    for i, ql in enumerate(np.asarray(q_len)):
        np.testing.assert_array_equal(np.asarray(got)[i, ql:], 0.0)


# --------------------------------------------- stall detection (engine)
@pytest.fixture(scope="module")
def small_model():
    cfg = tiny_serving_model(rank=8, num_layers=2, d_model=128,
                             vocab_size=512, num_heads=4, num_kv_heads=2)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    lora = tfm.init_lora_stacks(cfg, jax.random.PRNGKey(1), n_adapters=4)
    return cfg, params, lora


def test_stall_detection_fires_under_mixed_batching(small_model):
    """The §14 step restructure must keep the no-progress accounting: a
    request that can never allocate (pool pinned beyond its needs) still
    fails with ``stalled`` after ``stall_limit`` empty plans."""
    cfg, params, lora = small_model
    sc = ServeConfig(page_size=16, max_pages=12, max_batch=4,
                     max_prefill_tokens=48, max_pages_per_req=10,
                     stall_limit=6, mode="forkkv")
    assert sc.mixed_batching is True     # the default under test
    eng = Engine(cfg, params, lora, sc)
    rng = np.random.default_rng(0)
    ctx = Request(rid=1, adapter_id=0, max_new_tokens=0, is_context=True,
                  prompt=list(rng.integers(0, cfg.vocab_size, 96)))
    eng.submit(ctx)
    while ctx.state != "done":
        eng.step()
    pin = eng.pin_prefix(ctx.prompt, 0)          # 6 of 11 pages pinned
    big = Request(rid=2, adapter_id=1, max_new_tokens=4,
                  prompt=list(rng.integers(0, cfg.vocab_size, 120)))
    eng.submit(big)
    for _ in range(sc.stall_limit + 20):
        if big.state == "done":
            break
        eng.step()
    assert big.finish_reason == "stalled"
    assert "stalled" in big.error and big.output == []
    assert eng.metrics()["stalled"] == 1
    eng.unpin(pin)
