"""Paged-native serving decode (DESIGN.md §12): executor/engine behaviour.

Covers the shape-policy and phasing properties of the paged hot path:
  * compiled decode variants stay O(log max_batch) under a
    fluctuating-batch workload (power-of-two bucketing, no per-batch-size
    retraces);
  * batched prefill produces the same results as the seed's one-request-
    per-step chunking (implicitly: every test in the suite runs on it);
  * host time per engine phase and the executor's per-path counters are
    populated.

Paged-vs-gather token parity lives in tests/test_parity_matrix.py — the
canonical cross-mode gate over {mode} x {paged, gather} x {attention
flavour} (DESIGN.md §13) that replaced this file's ad-hoc parity test.
"""
import math

import jax
import numpy as np
import pytest

from repro.configs.paper_models import tiny_serving_model
from repro.core.config import ServeConfig
from repro.models import transformer as tfm
from repro.serving.api import ForkServer
from repro.serving.sampling import SamplingParams


@pytest.fixture(scope="module")
def model():
    cfg = tiny_serving_model(rank=8)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    lora = tfm.init_lora_stacks(cfg, jax.random.PRNGKey(1), n_adapters=16)
    return cfg, params, lora


def make_server(model, mode, *, paged=True, max_batch=4, max_pages=192,
                max_pages_per_req=12):
    cfg, params, lora = model
    sc = ServeConfig(page_size=16, max_pages=max_pages, max_batch=max_batch,
                     max_prefill_tokens=64, mode=mode,
                     max_pages_per_req=max_pages_per_req,
                     use_paged_kernel=paged)
    return ForkServer(cfg, params, lora, sc), cfg


def test_decode_jit_variants_logarithmic(model):
    """Fluctuating decode batch: requests with staggered generation
    lengths shrink the live batch 5 -> 1, but the executor buckets the
    compiled batch to powers of two (<= max_batch), so the number of
    compiled decode variants is bounded by log2(max_batch) + 1 — not by
    the number of distinct batch sizes seen."""
    max_batch = 8
    server, cfg = make_server(model, "forkkv", max_batch=max_batch)
    rng = np.random.default_rng(1)
    handles = []
    for i in range(5):
        prompt = list(rng.integers(0, cfg.vocab_size, 20 + i))
        handles.append(server.generate(
            i, prompt, SamplingParams(max_new_tokens=2 * i + 2)))
    outs = [o.tokens for o in server.wait(handles)]
    for i, toks in enumerate(outs):
        assert len(toks) == 2 * i + 2
    m = server.metrics()
    # batch sizes 5,4,3,2,1 were live; buckets {8,4,2,1} at most
    bound = int(math.log2(max_batch)) + 1
    assert 1 <= m["decode_jit_variants"] <= bound, m["decode_jit_variants"]
    # steady state: a second identical workload adds NO new variants
    before = m["decode_jit_variants"]
    hs = [server.generate(9, list(rng.integers(0, cfg.vocab_size, 24)),
                          SamplingParams(max_new_tokens=4))]
    server.wait(hs)
    assert server.metrics()["decode_jit_variants"] == before


def test_phase_metrics_populated(model):
    """Host time per engine phase and per executor path: the prompt ran
    through the mixed path and the tokens through the decode path, and
    the step's one sync happens once per step, not once per chunk."""
    server, cfg = make_server(model, "forkkv")
    rng = np.random.default_rng(2)
    h = server.generate(1, list(rng.integers(0, cfg.vocab_size, 40)),
                        SamplingParams(max_new_tokens=4))
    out = server.wait([h])[0]
    assert len(out.tokens) == 4
    m = server.metrics()
    ns = m["span_ns"]
    for phase in ("engine.step", "engine.admit", "scheduler.plan",
                  "engine.sync", "engine.commit"):
        assert ns[phase] > 0, phase
    paths = m["executor_calls"]
    assert paths["mixed"]["calls"] == 1
    assert paths["decode"]["calls"] >= 4
    for c in paths.values():
        assert c["prepare_ns"] > 0 and c["dispatch_ns"] > 0
    assert sum(c["calls"] for c in paths.values()) == m["steps"]
    assert m["host_ms_per_step"] > 0
    assert m["decode_steps"] >= 4


def test_batched_prefill_matches_sequential(model):
    """Batched multi-request prefill must not change outputs: N concurrent
    requests (co-scheduled chunks, one padded executor call) produce the
    same greedy tokens as the same prompts submitted one at a time."""
    cfg = model[0]
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(0, cfg.vocab_size, 30 + 7 * i))
               for i in range(3)]
    # concurrent: all three prefill together
    server, _ = make_server(model, "forkkv")
    hs = [server.generate(i + 1, p, SamplingParams(max_new_tokens=5))
          for i, p in enumerate(prompts)]
    concurrent = [o.tokens for o in server.wait(hs)]
    # sequential: fresh server, one request at a time (prefill batch = 1)
    server2, _ = make_server(model, "forkkv")
    sequential = []
    for i, p in enumerate(prompts):
        h = server2.generate(i + 1, p, SamplingParams(max_new_tokens=5))
        sequential.append(server2.wait([h])[0].tokens)
    assert concurrent == sequential
