"""Prefill cost vs prompt length: page-native prefill vs legacy gather.

The point of the page-native prefill path (DESIGN.md §13): the gather path
materializes every request's FULL block table for EVERY chunk — O(smax)
HBM traffic per chunk regardless of how many tokens the prompt actually
has — while the paged path's traffic tracks the live page count (bucketed
to powers of two).  So with ``smax`` fixed, gather per-token prefill cost
stays ~flat (pinned to smax) as the prompt shrinks, and paged per-token
cost drops with it.  Prefill is where shared-context agent workloads spend
their compute (PrefillShare / KVFlow), which is why this is the hot path
worth recording.

Method: for each (mode, path, ctx) cell, one ForkServer with a FIXED
``max_pages_per_req`` (so ``smax`` is identical across ctx values) prefills
one warm prompt (compiles the bucketed shapes) and then N DISTINCT fresh
prompts of the same length (radix misses, so prefill really recomputes);
the cell's cost is the delta of the engine's host time in ``engine.step``
(``span_ns``; the prefill steps and the one decode step that ends the
request, each with its blocking sync) per prompt token, min-of-N against
scheduler noise.

Emits CSV rows (benchmarks.run harness format) AND writes
``BENCH_prefill.json`` — recorded next to ``BENCH_decode.json`` in the
repo's perf trajectory (both are CI artifacts).

  python -m benchmarks.bench_prefill             # full sweep
  python -m benchmarks.bench_prefill --smoke     # CI-sized, same JSON
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

FULL = dict(ctxs=(64, 128, 256, 448), max_pages_per_req=32, max_pages=640,
            passes=3)
SMOKE = dict(ctxs=(48, 96), max_pages_per_req=8, max_pages=192, passes=2)


def _measure_cell(mode: str, paged: bool, ctx: int, knobs: Dict) -> Dict:
    cfg, params, lora = get_tiny_model(rank=8)
    sc = ServeConfig(page_size=16, max_pages=knobs["max_pages"],
                     max_batch=4, max_prefill_tokens=128, mode=mode,
                     max_pages_per_req=knobs["max_pages_per_req"],
                     use_paged_kernel=paged)
    server = ForkServer(cfg, params, lora, sc)
    rng = np.random.default_rng(0)
    sp = SamplingParams(max_new_tokens=1)

    def one_pass(seed_offset: int) -> float:
        """Prefill one fresh ctx-length prompt; return Δ engine.step ms."""
        prompt = list(rng.integers(0, cfg.vocab_size, ctx))
        m0 = server.metrics()
        out = server.wait([server.generate(1, prompt, sp)])[0]
        assert len(out.tokens) == 1, out
        return (server.metrics()["span_ns"].get("engine.step", 0) -
                m0["span_ns"].get("engine.step", 0)) / 1e6

    one_pass(0)                         # warm: compiles the bucket shapes
    per_tok_ms = min(one_pass(i + 1) for i in range(knobs["passes"])) / ctx
    m = server.metrics()
    if paged:                           # acceptance probe: truly page-native
        assert m["fallback_gather_calls"] == 0, m["fallback_gather_calls"]
    return {
        "mode": mode,
        "path": "paged" if paged else "gather",
        "ctx_tokens": ctx,
        "smax_tokens": knobs["max_pages_per_req"] * sc.page_size,
        "us_per_prompt_token": per_tok_ms * 1e3,
        "fallback_gather_calls": m["fallback_gather_calls"],
    }


# what a row's number is: BENCH_prefill.json files written without
# this key timed the prefill calls' enqueue only, so their
# us_per_prompt_token is not comparable with these
MEASURES = ("us_per_prompt_token: host time of engine.step (every "
            "phase, syncs included) per prompt token")


def run(smoke: bool) -> Dict:
    knobs = SMOKE if smoke else FULL
    rows: List[Dict] = []
    for mode in ("forkkv", "prefix"):
        for paged in (True, False):
            for ctx in knobs["ctxs"]:
                cell = _measure_cell(mode, paged, ctx, knobs)
                # each cell owns its own pools + jit cache; drop both so
                # later cells aren't measured under accumulated pressure
                gc.collect()
                jax.clear_caches()
                rows.append(cell)
                emit(f"prefill.{mode}.{cell['path']}.ctx{ctx}",
                     cell["us_per_prompt_token"],
                     f"smax={cell['smax_tokens']}")
    # scaling summary: per (mode, ctx extreme), paged per-token cost over
    # gather per-token cost — well below 1 at short ctx (gather pays smax,
    # paged pays live pages), converging toward 1 as ctx -> smax
    summary: Dict[str, float] = {}
    for mode in ("forkkv", "prefix"):
        sel = {p: [r for r in rows if r["mode"] == mode and r["path"] == p]
               for p in ("paged", "gather")}
        for tag, pick in (("short", min), ("long", max)):
            pg = pick(sel["paged"], key=lambda r: r["ctx_tokens"])
            ga = pick(sel["gather"], key=lambda r: r["ctx_tokens"])
            ratio = pg["us_per_prompt_token"] / \
                max(ga["us_per_prompt_token"], 1e-9)
            summary[f"{mode}.{tag}_ctx_paged_over_gather"] = round(ratio, 4)
            emit(f"prefill.{mode}.{tag}_paged_over_gather", 0, f"{ratio:.3f}")
    return {"smoke": smoke, "knobs": {k: list(v) if isinstance(v, tuple)
                                      else v for k, v in knobs.items()},
            "measures": MEASURES, "rows": rows, "summary": summary}


def main(argv=None) -> None:
    # benchmarks.run calls main() with no args while holding its own CLI
    # flags in sys.argv — parse only what we are explicitly handed
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized sweep (same JSON output)")
    ap.add_argument("--out", default="BENCH_prefill.json")
    args = ap.parse_args([] if argv is None else argv)
    report = run(args.smoke)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(f"# wrote {args.out}")


if __name__ == "__main__":
    import sys
    main(sys.argv[1:])
