"""Decode step latency vs context length: paged kernel vs legacy gather.

The point of the page-native decode path (DESIGN.md §12): the gather path
materializes every request's FULL block table — O(smax) HBM traffic per
step regardless of how many tokens the request actually has — while the
paged path's traffic tracks the live page count (bucketed to powers of
two).  So with ``smax`` fixed, gather step time should stay ~flat as the
context shrinks, and paged step time should drop with it.

Method: for each (mode, path, ctx) cell, one ForkServer with a FIXED
``max_pages_per_req`` (so ``smax`` is identical across ctx values) runs the
same fork twice — the first pass builds the cache and compiles every
bucket, the second is a full prefix hit, i.e. a pure-decode run — and the
cell's cost is the delta of the engine's host time in ``engine.step``
(``span_ns``, which includes the step's blocking sync) over the delta of
engine steps.

Emits CSV rows (benchmarks.run harness format) AND writes
``BENCH_decode.json`` — the start of this repo's recorded perf trajectory.

  python -m benchmarks.bench_decode             # full sweep
  python -m benchmarks.bench_decode --smoke     # CI-sized, same JSON
"""
from __future__ import annotations

import argparse
import gc
import json
from typing import Dict, List

import jax
import numpy as np

from benchmarks.common import emit, get_tiny_model
from repro.core.config import ServeConfig
from repro.serving.api import ForkServer
from repro.serving.sampling import SamplingParams

FULL = dict(ctxs=(64, 128, 256, 448), max_pages_per_req=32, max_new=48,
            max_pages=640)
SMOKE = dict(ctxs=(48, 96), max_pages_per_req=8, max_new=16, max_pages=192)


def _measure_cell(mode: str, paged: bool, ctx: int, knobs: Dict) -> Dict:
    cfg, params, lora = get_tiny_model(rank=8)
    sc = ServeConfig(page_size=16, max_pages=knobs["max_pages"],
                     max_batch=4, max_prefill_tokens=128, mode=mode,
                     max_pages_per_req=knobs["max_pages_per_req"],
                     use_paged_kernel=paged)
    server = ForkServer(cfg, params, lora, sc)
    rng = np.random.default_rng(0)
    context = list(rng.integers(0, cfg.vocab_size, ctx))
    instr = list(rng.integers(0, cfg.vocab_size, 8))
    sp = SamplingParams(max_new_tokens=knobs["max_new"])
    with server.session(context, adapter_id=0) as sess:
        # pass 1: prefill + decode — compiles every bucket, fills the cache
        warm = server.wait([sess.fork(1, instr, sp)])[0]
        # measured passes: full prefix hits -> pure decode, identical
        # greedy tokens.  min-of-N is robust to scheduler/GC noise spikes
        # (compile time dominates the cell anyway, not these steps).
        per_step_ms = []
        steps = 0
        m1 = server.metrics()
        for _ in range(3):
            m0 = m1
            out = server.wait([sess.fork(1, instr, sp)])[0]
            m1 = server.metrics()
            assert out.tokens == warm.tokens, "warm/measured runs diverged"
            steps = m1["steps"] - m0["steps"]
            ms = (m1["span_ns"].get("engine.step", 0) -
                  m0["span_ns"].get("engine.step", 0)) / 1e6
            per_step_ms.append(ms / max(1, steps))
    return {
        "mode": mode,
        "path": "paged" if paged else "gather",
        "ctx_tokens": ctx,
        "smax_tokens": knobs["max_pages_per_req"] * sc.page_size,
        "decode_steps": steps,
        "us_per_decode_step": min(per_step_ms) * 1e3,
        "decode_jit_variants": m1["decode_jit_variants"],
    }


# what a row's number is: BENCH_decode.json files written without
# this key timed the decode call plus its sync only, so their
# us_per_decode_step is not comparable with these
MEASURES = ("us_per_decode_step: host time of engine.step (admission, "
            "planning, the executor call, its sync, commit) per step")


def run(smoke: bool) -> Dict:
    knobs = SMOKE if smoke else FULL
    rows: List[Dict] = []
    for mode in ("forkkv", "prefix"):
        for paged in (True, False):
            for ctx in knobs["ctxs"]:
                cell = _measure_cell(mode, paged, ctx, knobs)
                # each cell owns ~100MB of pools + its own jit cache;
                # drop both so later cells aren't measured under the
                # accumulated allocation pressure of earlier ones
                gc.collect()
                jax.clear_caches()
                rows.append(cell)
                emit(f"decode.{mode}.{cell['path']}.ctx{ctx}",
                     cell["us_per_decode_step"],
                     f"smax={cell['smax_tokens']};steps="
                     f"{cell['decode_steps']}")
    # scaling summary: per (mode, path), step time at the shortest context
    # over step time at the longest — paged should be well below 1 (cost
    # tracks kv_len), gather should hover near 1 (cost pinned to smax)
    summary: Dict[str, float] = {}
    for mode in ("forkkv", "prefix"):
        for path in ("paged", "gather"):
            sel = [r for r in rows
                   if r["mode"] == mode and r["path"] == path]
            lo = min(sel, key=lambda r: r["ctx_tokens"])
            hi = max(sel, key=lambda r: r["ctx_tokens"])
            ratio = lo["us_per_decode_step"] / \
                max(hi["us_per_decode_step"], 1e-9)
            summary[f"{mode}.{path}.short_over_long_step_ratio"] = \
                round(ratio, 4)
            emit(f"decode.{mode}.{path}.short_over_long", 0,
                 f"{ratio:.3f}")
    return {"smoke": smoke, "knobs": {k: list(v) if isinstance(v, tuple)
                                      else v for k, v in knobs.items()},
            "measures": MEASURES, "rows": rows, "summary": summary}


def main(argv=None) -> None:
    # benchmarks.run calls main() with no args while holding its own CLI
    # flags in sys.argv — parse only what we are explicitly handed
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized sweep (same JSON output)")
    ap.add_argument("--out", default="BENCH_decode.json")
    args = ap.parse_args([] if argv is None else argv)
    report = run(args.smoke)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(f"# wrote {args.out}")


if __name__ == "__main__":
    import sys
    main(sys.argv[1:])
