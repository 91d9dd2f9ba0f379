"""One run of a cell: set-up, the measured window, the check.

Set-up makes the weights on the device, builds the server with a KV pool
that holds the mix's working set, runs each program the window can use once
by a direct executor call (the persistent compile cache serves them after a
cell's first run), prefills each shared document as a session, opens each
agent's own session on its document (the agent's residual KV of it) and
submits every agent's first turn.  The window opens once every agent has
had its first token, and lasts ``seconds``.  In it each agent, a closed-loop
client, takes its next turn the moment its reply ends.  After the window
closes, the turns submitted in it are waited for until their first token,
and then a sample of the turns the window finished is compared with the
plain reference of the configuration's architecture (``chipbench/arch/``).
With ``control`` the int8 control's picks stand in the served tokens' place
in that comparison.  The model is reached through that architecture module
alone: weights, server configuration, reference and work counts.

Host spans around the calls into each layer (``bench.window``,
``bench.poll``, ``bench.submit``, ``bench.exec.decode``,
``bench.exec.mixed``) go into the profiler's trace in ``--trace 1`` runs.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import statistics
import sys
import tempfile
import time
from types import ModuleType
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from chipbench import roofline, spec, trace as trace_mod
from chipbench.traffic import (Traffic, longest_context, pages_for, pow2,
                               shortest_context)

DRAIN_LIMIT_S = 60.0
GRIDS = ("paged_residual_attention_decode", "paged_residual_attention_mixed")
SPANS = ("bench.submit", "bench.poll", "bench.exec.decode",
         "bench.exec.mixed")
span = jax.profiler.TraceAnnotation


class CompileClock:
    """Programs compiled or loaded from the persistent cache, from JAX's
    monitoring events."""

    def __init__(self):
        self.events: List[tuple] = []  # (host time, function, s, cache hit)
        self._hit = False

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.events.append((time.perf_counter(),
                                    kw.get("fun_name", "?"), duration,
                                    self._hit))
                self._hit = False

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self._hit = True
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def since(self, t: float) -> List[str]:
        return [name for when, name, _, _ in self.events if when >= t]

    def summary(self) -> Dict:
        missed = [(n, round(s, 3)) for _, n, s, hit in self.events
                  if not hit]
        return {"programs": len(self.events),
                "seconds": sum(s for _, _, s, _ in self.events),
                "cache_hits": sum(1 for e in self.events if e[3]),
                "compiled": missed}


@dataclasses.dataclass
class TurnRecord:
    agent: int
    adapter: int
    prompt: List[int]
    max_new: int
    submit_t: float
    receipts: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    finish: str = ""
    finished_t: float = 0.0


class ExecLog:
    """Wraps the executor's one step entry (``mixed_step``, which runs a
    decode-only plan through the decode grid and any other plan through
    the mixed grid) in a host span, and records each call's rows while
    ``on``."""

    def __init__(self, executor):
        self.on = False
        self.calls: List[tuple] = []        # (host time, kind, rows)
        inner = executor.mixed_step
        max_batch = executor.sc.max_batch

        def mixed_step(chunks, starts, adapter_ids, base_tables, res_tables,
                       *args, **kwargs):
            decode = (not kwargs.get("verify") and len(chunks) <= max_batch
                      and all(len(c) == 1 for c in chunks))
            kind = "decode" if decode else "mixed"
            if self.on:
                self.calls.append((time.perf_counter(), kind, tuple(
                    roofline.Row(int(s), len(c), tuple(bt))
                    for c, s, bt in zip(chunks, starts, base_tables))))
            with span(f"bench.exec.{kind}"):
                return inner(chunks, starts, adapter_ids, base_tables,
                             res_tables, *args, **kwargs)
        executor.mixed_step = mixed_step


class Clients:
    """The closed-loop agents: each submits its next turn as soon as its
    previous reply has ended."""

    def __init__(self, server, sessions: Dict, sampling):
        """``sessions``: each agent's own ``AgentSession`` on its
        document, by agent index; a turn that forks from the document
        forks that session."""
        self.server = server
        self.sessions = sessions
        self.sampling = sampling
        self.open = True                 # submit follow-up turns
        self.records: List[TurnRecord] = []
        self.live: Dict[int, tuple] = {}  # rid -> (agent, turn, record)
        self.first_token = set()          # agents with a token received
        pool = server.engine
        self.pools = (pool.base_pool, pool.res_pool)
        self.peak_share = 0.0

    def submit(self, agent) -> None:
        with span("bench.submit"):
            turn = agent.next_turn()
            sp = self.sampling(max_new_tokens=turn.max_new)
            if turn.first_of_fork:
                h = self.sessions[agent.index].fork(turn.adapter,
                                                    turn.new_tokens, sp)
            else:
                h = self.server.generate(turn.adapter, turn.prompt, sp)
            rec = TurnRecord(agent.index, turn.adapter, turn.prompt,
                             turn.max_new, time.perf_counter())
            self.records.append(rec)
            self.live[h.rid] = (agent, turn, rec)

    def poll(self) -> None:
        with span("bench.poll"):
            events = self.server.poll()
        now = time.perf_counter()
        for ev in events:
            agent, turn, rec = self.live[ev.rid]
            if ev.finished:
                rec.finish, rec.finished_t = ev.finish_reason, now
                del self.live[ev.rid]
                agent.finish_turn(turn, rec.tokens)
                if self.open:
                    self.submit(agent)
            else:
                rec.receipts.append(now)
                rec.tokens.append(ev.token)
                self.first_token.add(agent.index)
        share = max(p.used_pages / p.num_pages for p in self.pools)
        self.peak_share = max(self.peak_share, share)


@dataclasses.dataclass
class Run:
    """What a run measured; the metric readers (``chipbench/metrics``)
    read it."""
    t0: float
    t1: float
    records: List[TurnRecord]
    setup_s: float
    phases: Dict[str, float]
    arch: ModuleType                 # the configuration's architecture
    dims: Any                        # its sizes, for its own functions
    page: int
    kv_pages: int
    kv_peak_share: float
    counters: tuple                  # engine counters at t0 and t1
    exec_calls: List[tuple]          # executor calls made in the window
    peak: Optional[Dict[str, float]] = None
    trace: Optional[trace_mod.Summary] = None

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def in_window(self, t: float) -> bool:
        return self.t0 <= t < self.t1

    def tokens(self) -> int:
        return sum(1 for r in self.records for t in r.receipts
                   if self.in_window(t))

    def gaps_s(self) -> List[float]:
        """Every gap between consecutive tokens of a turn that ends in
        the window."""
        return [b - a for r in self.records
                for a, b in zip(r.receipts, r.receipts[1:])
                if self.in_window(b)]

    def ttfts_s(self) -> List[float]:
        """Submission to first token, for every turn submitted in the
        window."""
        return [r.receipts[0] - r.submit_t for r in self.records
                if self.in_window(r.submit_t) and r.receipts]

    def submitted(self) -> List[TurnRecord]:
        return [r for r in self.records if self.in_window(r.submit_t)]


def percentile(values: List[float], q: float) -> float:
    """Linear interpolation between order statistics (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def median(values: List[float]) -> float:
    return float(statistics.median(values))


def _counters(server) -> Dict:
    eng = server.engine
    m = eng.metrics()
    return {"prefilled": float(m["prefilled_tokens"]),
            "prompt": float(m["prompt_tokens"]),
            "evicted_pages": m["evicted_pages"],
            "preempted": m["preempted_requests"]}


def _warm(server, traffic: Traffic, conf: Dict) -> int:
    """Run each step program the window can use once, by direct executor
    calls that write only the executor's scratch page: one row per agent,
    the longest row reaching from the shortest to the longest context of
    the mix (each power of two of pages crossed), and a decode step or a
    prefill chunk of each power of two from 32 tokens (the mixed grid's
    smallest query tile) up to the chunk cap.  Returns the number of
    calls."""
    ex = server.engine.executor
    page = ex.page
    n = len(traffic.agents)
    lo = pages_for(shortest_context(traffic.mix) + 1, page)
    hi = min(pages_for(longest_context(traffic.mix), page),
             ex.max_pages_per_req)
    extents = {lo, hi} | {2 ** k + 1 for k in range(12)
                          if lo <= 2 ** k + 1 <= hi}
    cap = conf["serve"]["max_prefill_tokens"]
    qs = sorted({1, cap} | {2 ** k for k in range(5, 12) if 2 ** k <= cap})
    calls = 0
    for ext in sorted(extents):
        for q in qs:
            start = ext * page - q
            chunks = [[0] * q] + [[0]] * (n - 1)
            starts = [start] + [ext * page - 1] * (n - 1)
            out = ex.mixed_step(
                chunks, starts, [1] * n, [[ex.dump_page] * ext] * n,
                [[ex.dump_page_r] * ext] * n,
                [[ex.dump_page] * len(c) for c in chunks],
                [[ex.dump_page_r] * len(c) for c in chunks])
            jax.block_until_ready(out)
            calls += 1
    return calls


def _free(server) -> None:
    """Release the server's KV pools before the reference runs."""
    ex = server.engine.executor
    for arr in ex.pools:
        if arr is not None:
            arr.delete()
    ex.pools = None


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, device, control: bool = False) -> Dict:
    """One run of ``cell``.  Returns the result dict; its last key,
    ``checks``, holds each compared number with its limit."""
    from chipbench import serve
    clock = CompileClock()
    conf, mix, arch = cell.config, cell.traffic, cell.arch
    dims = arch.dims_of(conf)
    phases: Dict[str, float] = {}
    t = time.perf_counter()
    phases["start"] = t - t_start

    traffic = Traffic(mix, seed, dims.vocab)
    params, lora = arch.make_weights(dims, traffic.n_adapters, seed)
    phases["weights"] = time.perf_counter() - t
    t = time.perf_counter()

    cfg = arch.model_config(conf, dims)
    server, kv_pages = serve.build_server(conf, mix, cfg, params, lora)
    ex = server.engine.executor
    phases["server"] = time.perf_counter() - t
    t = time.perf_counter()

    warm_calls = _warm(server, traffic, conf)
    log = ExecLog(ex)
    phases["warm"] = time.perf_counter() - t
    t = time.perf_counter()

    # each group's document once under the sessions' adapter 0 (its base
    # KV is what every fork of the group inherits), then each agent's own
    # fork of its document: one request at a time, so the residual prefill
    # of every agent's first fork is not padded to the window's batch
    groups = {}
    if traffic.sessions:
        for g, doc in enumerate(traffic.docs):
            groups[g] = server.session(doc, adapter_id=0)
    sessions = {a.index: server.session(a.doc, adapter_id=a.adapter)
                for a in traffic.agents}
    phases["sessions"] = time.perf_counter() - t
    t = time.perf_counter()

    clients = Clients(server, sessions, serve.SamplingParams)
    for agent in traffic.agents:
        clients.submit(agent)
    while len(clients.first_token) < len(traffic.agents):
        clients.poll()
    phases["ramp"] = time.perf_counter() - t
    ramp_turns = len(clients.records)

    tmp = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tmp, profiler_options=opts)
    c0 = _counters(server)
    log.on = True
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    with span(trace_mod.WINDOW):
        t1 = t0 + seconds
        while time.perf_counter() < t1:
            clients.poll()
    log.on = False
    c1 = _counters(server)
    if trace:
        jax.profiler.stop_trace()
    clients.open = False
    drain_end = time.perf_counter() + DRAIN_LIMIT_S
    waiting = [r for r in clients.records if t0 <= r.submit_t < t1]
    while (any(not r.receipts for r in waiting)
           and time.perf_counter() < drain_end):
        clients.poll()
    compiled_in_window = clock.since(t0)
    stats = device.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", -1))

    run = Run(t0=t0, t1=t1, records=clients.records, setup_s=setup_s,
              phases=phases, arch=arch, dims=dims, page=ex.page,
              kv_pages=kv_pages, kv_peak_share=clients.peak_share,
              counters=(c0, c1),
              exec_calls=[c for c in log.calls if t0 <= c[0] < t1])
    _free(server)
    del server, clients, sessions, groups, ex, log
    gc.collect()

    summary = None
    if trace:
        devices, spans = trace_mod.load(tmp)
        shutil.rmtree(tmp, ignore_errors=True)
        summary = trace_mod.reduce(devices, spans)
        run.trace = summary
        try:
            run.peak = roofline.peaks(device.device_kind)
        except KeyError:
            run.peak = None

    metrics = {}
    for m in cell.metrics(trace):
        value = spec.load_reader(m.name)(run)
        if value is not None:
            metrics[m.name] = {"value": float(value), "unit": m.unit}

    readings = compare(run, traffic, params, lora, conf, mix, seed, control)
    limits = conf["limits"]
    gap = readings["control_mean_logit_gap" if control else "mean_logit_gap"]
    checks = {
        "mean_logit_gap": {"value": gap, "limit": limits["mean_logit_gap"]},
        "compiles_in_window": {"value": len(compiled_in_window),
                               "limit": 0},
        "failed_turns": {"value": sum(
            1 for r in run.submitted()
            if r.finish and r.finish not in ("length", "stop")), "limit": 0},
    }
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())

    submitted = run.submitted()
    result = {
        "correct": correct,
        "attempted": len(submitted),
        "failed": checks["failed_turns"]["value"],
        "metrics": metrics,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": jax.device_count(),
                   "memory_peak_bytes": peak_bytes},
    }
    if trace and summary is not None:
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": trace_mod.top(summary.op_s, 10, GRIDS),
            "idle_gaps": trace_mod.top(summary.idle_gaps, 10, SPANS)}
    result["checks"] = checks
    result["_log"] = {
        "setup_s": setup_s, "phases": phases, "warm_calls": warm_calls,
        "ramp_turns": ramp_turns, "kv_pages": kv_pages,
        "compiles": clock.summary(),
        "compiled_in_window": compiled_in_window,
        "samples": {"turns_submitted": len(submitted),
                    "turns_with_first_token": len(run.ttfts_s()),
                    "gaps": len(run.gaps_s()),
                    "gaps_beyond_p99": len(run.gaps_s()) // 100,
                    "tokens": run.tokens()},
        "engine": {k: run.counters[1][k] for k in ("evicted_pages",
                                                   "preempted")},
        "readings": readings}
    return result


def compare(run: Run, traffic: Traffic, params, lora, conf: Dict,
            mix: Dict, seed: int, control: bool) -> Dict:
    """Served tokens of a sample of the turns the window finished against
    the reference: how far each served token's logit lies below the
    reference's best, as the mean over the sample (the number compared)
    and the widest; with ``control``, the same for the int8 control's
    picks at the same positions."""
    finished = [r for r in run.records if r.finish == "length" and r.tokens
                and run.in_window(r.finished_t)]
    cmp = conf["compare"]
    sample = traffic.sample(finished, seed, cmp["min_tokens"],
                            cmp["max_requests"])
    s_pad = pow2(pages_for(longest_context(mix), run.page)) * run.page
    t_pad = mix["output_tokens"]["max"]
    prog, ctrl = [], []
    t = time.perf_counter()
    for rec in sample:
        agent = traffic.agents[rec.agent]
        doc = agent.doc if traffic.sessions else []
        p, c = run.arch.gaps(params, lora, run.dims, rec.adapter,
                             rec.prompt, rec.tokens, doc, s_pad, t_pad,
                             control=control)
        prog.append(p)
        ctrl.append(c)
    prog = np.concatenate(prog) if prog else np.zeros(0)
    ctrl = np.concatenate(ctrl) if ctrl else np.zeros(0)
    out = {"requests": len(sample), "compared_tokens": int(prog.size),
           "mean_logit_gap": float(prog.mean()) if prog.size else None,
           "max_logit_gap": float(prog.max()) if prog.size else None,
           "seconds": time.perf_counter() - t}
    if control:
        out["control_mean_logit_gap"] = (float(ctrl.mean()) if ctrl.size
                                         else None)
        out["control_max_logit_gap"] = (float(ctrl.max()) if ctrl.size
                                        else None)
    return out


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def cache_dir() -> str:
    """JAX's persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR`` when
    set, else ``.jax_cache`` at the checkout's root (a fixed path: the
    path is part of the cache's key)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(spec.ROOT / ".jax_cache"))
