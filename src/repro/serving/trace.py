"""Host spans on the profiler's clock, and the counters behind them.

Two parts with one switch, the profiler itself:

- ``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation`` while the
  profiler records (``TraceMe.is_enabled()``) and the shared no-op ``NOOP``
  otherwise, so a trace shows the program's own host spans on the same
  clock as the device's operations, and costs nothing when nobody records.
- ``Clock.span`` opens the same context and adds its wall time
  (``time.perf_counter_ns``) to a cumulative counter under the span's
  name, always; ``ExecCounters.span`` does the same for a call's
  ``executor.prepare`` and ``executor.dispatch``, keyed by the executor's
  path.  ``ExecCounters`` also keeps the per-path shape counters: how much
  of each query tile and block-table walk is live.  These counters are
  what ``Engine.metrics()`` (and so ``serve.py --stats`` and
  ``GET /v1/metrics``) shows an operator.  The ``api.*`` spans are
  profiler-only (``span``): they carry no counter.

Span names, outermost first (children nest in their parents):

- ``api.poll``, ``api.generate``, ``api.fork``, ``api.session``
  (``ForkServer``, ``AgentSession``; metadata ``rid``);
- ``engine.step`` (a step annotation, metadata ``step_num``), inside it
  ``engine.admit`` (expiry, shedding, admission, the preempt trigger;
  metadata ``rids`` admitted), ``scheduler.plan`` (``rows``, ``tokens``),
  one ``executor.<path>`` per executor call, ``engine.sync`` (the step's
  blocking device-to-host read) and ``engine.commit`` (token bookkeeping,
  finishes, radix commits);
- inside ``executor.<path>`` (metadata ``bpad``, ``qpad``, ``width``):
  ``executor.prepare`` (shape policy, padded tables and inputs, the host
  to device copies) and ``executor.dispatch`` (the jitted call).

Executor paths: ``decode`` (the decode grid), ``mixed`` (the unified
prefill/decode grid), ``verify`` (the unified grid with speculative verify
rows), ``prefill`` (the phase-separated loop's batched prefill) and
``broadcast`` (one base pass for a broadcast fork's group).  Nothing here
runs inside a jitted body.
"""
from __future__ import annotations

from time import perf_counter_ns
from typing import Dict

from jax.profiler import StepTraceAnnotation, TraceAnnotation

EXEC_FIELDS = ("calls", "live_tokens", "slots", "live_pages",
               "walked_pages", "prepare_ns", "dispatch_ns")


class _Noop:
    """The context ``span`` returns while the profiler is off."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **meta) -> None:
        pass


NOOP = _Noop()


def recording() -> bool:
    """Whether the profiler records host spans now."""
    return TraceAnnotation.is_enabled()


def span(name: str, **meta):
    """``name`` as a profiler span while the profiler records, else
    ``NOOP``."""
    if TraceAnnotation.is_enabled():
        return TraceAnnotation(name, **meta)
    return NOOP


class _Timed:
    """A span whose wall time is added to ``ns[key]`` on exit; ``ann`` is
    the profiler's annotation, or ``NOOP``."""
    __slots__ = ("ns", "key", "ann", "t0")

    def __init__(self, ns: Dict[str, int], key: str, ann):
        self.ns, self.key, self.ann = ns, key, ann

    def __enter__(self):
        if self.ann is not NOOP:
            self.ann.__enter__()
        self.t0 = perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dt = perf_counter_ns() - self.t0
        ns = self.ns
        ns[self.key] = ns.get(self.key, 0) + dt
        if self.ann is not NOOP:
            self.ann.__exit__(*exc)
        return False

    def set_metadata(self, **meta) -> None:
        if self.ann is not NOOP:
            self.ann.set_metadata(**meta)


class Clock:
    """Cumulative host nanoseconds per span name."""

    def __init__(self):
        self.ns: Dict[str, int] = {}

    def span(self, name: str, **meta) -> _Timed:
        return _Timed(self.ns, name, span(name, **meta))

    def step(self, name: str, step: int) -> _Timed:
        """A step annotation (``jax.profiler.StepTraceAnnotation``)."""
        return _Timed(self.ns, name, StepTraceAnnotation(name, step_num=step)
                      if TraceAnnotation.is_enabled() else NOOP)


class ExecCounters:
    """Per executor path, cumulative: ``calls``; ``live_tokens`` (the
    rows' query lengths) of ``slots`` (padded batch x query tile, 1 for
    decode); ``live_pages`` (each live row's pages once its tokens are
    written) of ``walked_pages`` (padded batch x block-table width);
    ``prepare_ns`` and ``dispatch_ns``, the host time of the call's
    ``executor.prepare`` and ``executor.dispatch`` spans."""

    def __init__(self):
        self.paths: Dict[str, Dict[str, int]] = {}

    def _path(self, path: str) -> Dict[str, int]:
        c = self.paths.get(path)
        if c is None:
            c = self.paths[path] = dict.fromkeys(EXEC_FIELDS, 0)
        return c

    def span(self, path: str, part: str) -> _Timed:
        """``executor.<part>`` (``prepare`` or ``dispatch``) of a call on
        ``path``; its host time adds to the path's ``<part>_ns``."""
        return _Timed(self._path(path), part + "_ns",
                      span("executor." + part))

    def count(self, path: str, rows: int, qpad: int, width: int,
              live_tokens: int, live_pages: int) -> None:
        """One call on ``path`` of ``rows`` padded rows, a ``qpad`` query
        tile and a ``width``-page block table."""
        c = self._path(path)
        c["calls"] += 1
        c["live_tokens"] += live_tokens
        c["slots"] += rows * qpad
        c["live_pages"] += live_pages
        c["walked_pages"] += rows * width

    def host_ns(self) -> int:
        """Host ns of every call's preparation and dispatch so far."""
        return sum(c["prepare_ns"] + c["dispatch_ns"]
                   for c in self.paths.values())
