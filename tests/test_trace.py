"""Host spans and counters (serving/trace.py, DESIGN.md §12).

  * with the profiler off, ``span`` is the shared no-op and the clocks
    still count, at a cost far below a step's budget;
  * a hand-built decode or mixed call counts exact live and padded
    tokens and pages;
  * after a served run the per-path calls add up to the executor's calls
    and the engine's steps, live never exceeds padded, and the shares in
    ``Engine.metrics()`` are read from the counters;
  * ``serve.py --stats`` prints every counter;
  * under the profiler the spans sit on the host plane of the trace,
    nested ``engine.step`` ⊃ ``executor.<path>`` ⊃ ``executor.prepare``.
"""
import glob
import os
import time

import jax
import numpy as np
import pytest

from repro.configs.paper_models import tiny_serving_model
from repro.core.config import ServeConfig
from repro.models import transformer as tfm
from repro.serving import trace
from repro.serving.api import ForkServer
from repro.serving.sampling import SamplingParams


@pytest.fixture(scope="module")
def model():
    cfg = tiny_serving_model(rank=8)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    lora = tfm.init_lora_stacks(cfg, jax.random.PRNGKey(1), n_adapters=8)
    return cfg, params, lora


def make_server(model, max_batch=4, **knobs):
    cfg, params, lora = model
    sc = ServeConfig(page_size=16, max_pages=96, max_batch=max_batch,
                     max_prefill_tokens=64, mode="forkkv",
                     max_pages_per_req=12, **knobs)
    return ForkServer(cfg, params, lora, sc)


def serve_forks(server, cfg, n_forks=2, max_new=3):
    """A session on a 40-token context and ``n_forks`` forks of it."""
    rng = np.random.default_rng(0)
    sess = server.session(list(rng.integers(0, cfg.vocab_size, 40)))
    hs = [sess.fork(i + 1, list(rng.integers(0, cfg.vocab_size, 5 + i)),
                    SamplingParams(max_new_tokens=max_new))
          for i in range(n_forks)]
    return server.wait(hs)


def test_profiler_off_spans_are_the_shared_noop_and_clocks_count():
    assert not trace.recording()
    assert trace.span("engine.admit", rid=3) is trace.NOOP
    clock = trace.Clock()
    with clock.step("engine.step", 1) as st:
        assert st.ann is trace.NOOP
        with clock.span("engine.sync") as sp:
            assert sp.ann is trace.NOOP
            sp.set_metadata(rows=2)         # a no-op, not an error
            time.sleep(0.002)
        with clock.span("engine.sync"):
            pass
    assert clock.ns["engine.sync"] >= 2_000_000
    assert clock.ns["engine.step"] >= clock.ns["engine.sync"]
    assert set(clock.ns) == {"engine.step", "engine.sync"}


def test_profiler_off_cost_per_step_is_microseconds():
    """One engine step's spans and counters with the profiler off: the
    poll's profiler-only span, a step annotation, four engine spans, an
    executor span with its prepare and dispatch, the counters of a 16-row
    call.  The budget is 50 us a step
    (a step lost in a 51 s window moves throughput by 1%)."""
    clock, counters = trace.Clock(), trace.ExecCounters()
    starts = list(range(2100, 2116))

    def one_step():
        with trace.span("api.poll"), clock.step("engine.step", 1):
            for name in ("engine.admit", "scheduler.plan"):
                with clock.span(name) as sp:
                    sp.set_metadata(rows=16, tokens=16)
            with trace.span("executor.decode") as sp:
                with counters.span("decode", "prepare"):
                    live = sum(k // 16 + 1 for k in starts)
                with counters.span("decode", "dispatch"):
                    pass
                counters.count("decode", rows=16, qpad=1, width=256,
                               live_tokens=16, live_pages=live)
                sp.set_metadata(bpad=16, qpad=1, width=256)
            for name in ("engine.sync", "engine.commit"):
                with clock.span(name):
                    pass

    n = 2000
    per_step = []
    for _ in range(5):
        t = time.perf_counter_ns()
        for _ in range(n):
            one_step()
        per_step.append((time.perf_counter_ns() - t) / n)
    assert min(per_step) < 50_000, per_step
    assert counters.paths["decode"]["calls"] == 5 * n


def test_decode_call_counts_exact_pages(model):
    """Two rows at kv_len 20 and 40 with 16-token pages reach 2 + 3 pages
    once their tokens are written; the grid walks bpad x the bucket of 3
    pages."""
    ex = make_server(model).engine.executor
    d = ex.dump_page
    jax.block_until_ready(ex.decode(
        [1, 2], [20, 40], [1, 2], [[d] * 2, [d] * 3],
        [[ex.dump_page_r] * 2, [ex.dump_page_r] * 3], [d, d],
        [ex.dump_page_r] * 2, [20 % 16, 40 % 16]))
    c = ex.counters.paths["decode"]
    assert c["calls"] == 1
    assert (c["live_tokens"], c["slots"]) == (2, 2)
    assert c["live_pages"] == 5
    assert c["walked_pages"] == 2 * ex._bucket_width(3)
    assert c["prepare_ns"] > 0 and c["dispatch_ns"] > 0
    assert set(ex.counters.paths) == {"decode"}


def test_mixed_call_counts_exact_slots(model):
    """A decode row at kv_len 20 beside a 20-token prefill chunk: the
    batch pads to 4 rows (the floor at max_batch 4) and the query tile to
    32, so 21 of 128 slots are live; pages 2 + 2 of 4 x bucket(2)."""
    ex = make_server(model).engine.executor
    d, dr = ex.dump_page, ex.dump_page_r
    out = ex.mixed_step([[7], list(range(20))], [20, 0], [1, 2],
                        [[d] * 2, [d] * 2], [[dr] * 2, [dr] * 2],
                        [[d], [d] * 20], [[dr], [dr] * 20])
    jax.block_until_ready(out)
    c = ex.counters.paths["mixed"]
    assert c["calls"] == 1
    assert (c["live_tokens"], c["slots"]) == (21, 4 * 32)
    assert c["live_pages"] == 2 + 2
    assert c["walked_pages"] == 4 * ex._bucket_width(2)
    assert "decode" not in ex.counters.paths


@pytest.mark.parametrize("knobs,path", [
    (dict(mixed_batching=False), "prefill"),
    (dict(mixed_batching=False, broadcast_fork=True), "broadcast"),
    (dict(speculate=True, spec_k=2), "verify"),
], ids=["phase-separated", "broadcast", "verify"])
def test_every_path_taken_is_counted(model, knobs, path):
    """Three agents on one periodic 64-token prompt: the phase-separated
    loop's batched prefill, a broadcast fork's one base pass, and
    speculative verify rows (the prompt's period makes prompt lookup
    propose) each count on their own path."""
    server = make_server(model, **knobs)
    prompt = [5, 9, 2, 7] * 16
    outs = server.wait([server.generate(i + 1, prompt,
                                        SamplingParams(max_new_tokens=4))
                        for i in range(3)])
    assert all(len(o.tokens) == 4 for o in outs)
    m = server.metrics()
    paths = m["executor_calls"]
    assert paths[path]["calls"] > 0, sorted(paths)
    for c in paths.values():
        assert 0 < c["live_tokens"] <= c["slots"]
        assert 0 < c["live_pages"] <= c["walked_pages"]


def test_served_run_counters_add_up(model):
    cfg = model[0]
    server = make_server(model)
    ex = server.engine.executor
    m0 = server.metrics()
    assert m0["span_ns"] == {} and m0["executor_calls"] == {}
    assert m0["host_ms_per_step"] is None
    assert m0["mixed_slot_share"] is None
    assert m0["decode_walk_share"] is None
    # count the jitted calls from outside the counters
    seen, jitted = [], {n: getattr(ex, n) for n in ("_decode", "_prefill")}
    for name, fn in jitted.items():

        def counted(*a, _fn=fn, **kw):
            seen.append(1)
            return _fn(*a, **kw)
        setattr(ex, name, counted)
    outs = serve_forks(server, cfg)
    for name, fn in jitted.items():
        setattr(ex, name, fn)
    assert [len(o.tokens) for o in outs] == [3, 3]
    m = server.metrics()
    paths = m["executor_calls"]
    assert {"decode", "mixed"} <= set(paths)
    assert sum(c["calls"] for c in paths.values()) == len(seen) == m["steps"]
    for c in paths.values():
        assert c["live_tokens"] <= c["slots"]
        assert 0 < c["live_pages"] <= c["walked_pages"]
    ns = m["span_ns"]
    assert set(ns) == {"engine.step", "engine.admit", "scheduler.plan",
                       "engine.sync", "engine.commit"}   # api.*: no counter
    assert all(v > 0 for v in ns.values()), ns
    assert ns["engine.step"] >= (
        ns["engine.admit"] + ns["scheduler.plan"] + ns["engine.sync"]
        + ns["engine.commit"])
    assert m["host_ms_per_step"] == pytest.approx(
        (ns["engine.step"] - ns["engine.sync"]) / m["steps"] / 1e6)
    calls = sum(c["calls"] for c in paths.values())
    prepare = sum(c["prepare_ns"] for c in paths.values())
    assert m["prepare_ms_per_call"] == pytest.approx(prepare / calls / 1e6)
    assert ex.counters.host_ns() == prepare + sum(
        c["dispatch_ns"] for c in paths.values())
    mixed, decode = paths["mixed"], paths["decode"]
    assert m["mixed_slot_share"] == mixed["live_tokens"] / mixed["slots"]
    assert m["decode_walk_share"] == (decode["live_pages"]
                                      / decode["walked_pages"])
    assert 0 < m["decode_walk_share"] <= 1


def test_stats_lines_read_every_counter(model):
    """``serve.py --stats`` prints host ms per phase and, per executor
    path, the calls, both live shares and the prepare and dispatch ms."""
    from repro.launch.serve import _exec_path, _phases
    server = make_server(model)
    serve_forks(server, model[0])
    m = server.metrics()
    line = _phases(m)
    for key in ("step_ms", "admit_ms", "plan_ms", "prepare_ms",
                "dispatch_ms", "sync_ms", "commit_ms", "host_ms_per_step"):
        assert f"{key}=" in line, (key, line)
    dec = m["executor_calls"]["decode"]
    assert _exec_path("decode", dec) == (
        f"executor[decode] calls={dec['calls']} slot_share=100.0% "
        f"walk_share={100 * dec['live_pages'] / dec['walked_pages']:.1f}% "
        f"prepare_ms={dec['prepare_ns'] / 1e6:.1f} "
        f"dispatch_ms={dec['dispatch_ns'] / 1e6:.1f}")


def test_spans_nest_on_the_host_plane_of_a_trace(model, tmp_path):
    from jax.profiler import ProfileData
    cfg = model[0]
    server = make_server(model)
    serve_forks(server, cfg, n_forks=1, max_new=1)   # compile outside
    with jax.profiler.trace(str(tmp_path)):
        assert trace.recording()
        assert trace.span("x") is not trace.NOOP
        serve_forks(server, cfg, n_forks=2, max_new=2)
    paths = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert paths
    events = [e for p in ProfileData.from_file(paths[0]).planes
              if p.name.startswith("/host:") for line in p.lines
              for e in line.events
              if e.name.split(".")[0] in ("api", "engine", "scheduler",
                                          "executor")]
    by = {}
    for e in events:
        by.setdefault(e.name, []).append(e)
    for name in ("api.poll", "api.session", "api.fork", "api.generate",
                 "engine.step", "engine.admit", "scheduler.plan",
                 "engine.sync", "engine.commit", "executor.prepare",
                 "executor.dispatch"):
        assert name in by, (name, sorted(by))
    calls = [e for n, es in by.items() if n in ("executor.decode",
                                                "executor.mixed")
             for e in es]
    assert calls

    def within(inner, outer):
        return (outer.start_ns <= inner.start_ns and
                inner.start_ns + inner.duration_ns
                <= outer.start_ns + outer.duration_ns)

    for e in by["executor.prepare"] + by["executor.dispatch"]:
        assert any(within(e, c) for c in calls), e.name
    for c in calls:
        assert any(within(c, s) for s in by["engine.step"])
        assert {k for k, _ in c.stats} >= {"bpad", "qpad", "width"}
    for s in by["engine.step"]:
        assert any(within(s, p) for p in by["api.poll"])
        assert "step_num" in {k for k, _ in s.stats}
