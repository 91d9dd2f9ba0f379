"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  python -m benchmarks.run              # all benchmarks
  python -m benchmarks.run --only memory,throughput
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.launch.compile_cache import use_compile_cache

SECTIONS = ("memory", "throughput", "internals", "quality", "sensitivity",
            "kernel", "roofline", "tiering", "decode", "prefill")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    use_compile_cache()
    wanted = args.only.split(",") if args.only else list(SECTIONS)

    print("name,us_per_call,derived")
    for section in SECTIONS:
        if section not in wanted:
            continue
        mod = __import__(f"benchmarks.bench_{section}",
                         fromlist=["main"])
        t0 = time.time()
        try:
            mod.main()
        except Exception as e:   # keep the harness running
            print(f"bench_{section}.ERROR,0,{type(e).__name__}:{e}",
                  file=sys.stdout)
        print(f"bench_{section}.total,{(time.time()-t0)*1e6:.0f},ok")


if __name__ == "__main__":
    main()
