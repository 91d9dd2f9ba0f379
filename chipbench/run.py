"""Run one cell of the on-chip benchmark.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the checkout's root, on a machine that holds the chips the cell asks
for (``BENCHMARK.json``).  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiler trace of the
window.  The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number with its limit);
the counts behind each percentile are on the line before it.  The last
lines of standard error repeat the checks.

It exits 3, printing no result, when JAX finds no TPU or fewer chips than
the cell needs, or when the attention kernels would not run compiled for
the chip.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="judge the int8 control's picks in the served "
                         "tokens' place (chipbench/reference.py): a sound "
                         "limit reads correct false")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if args.seed < 0:
        print("--seed must be >= 0", file=sys.stderr)
        return 2
    from chipbench import spec
    cell = spec.load_cell(args.workload)

    import jax
    from chipbench import harness
    jax.config.update("jax_compilation_cache_dir", harness.cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        harness.log(f"needs {cell.chips} TPU chip(s); JAX found "
                    f"{len(devices)} {devices[0].platform} device(s)")
        return 3
    from chipbench import serve
    backend = serve.kernel_backend()
    if backend != "pallas":
        harness.log(f"attention kernels would run as {backend!r}, not "
                    f"compiled for the chip")
        return 3

    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START, devices[0],
                              control=args.control)
    info = result.pop("_log")
    ph = ", ".join(f"{k} {v:.3f}s" for k, v in info["phases"].items())
    c = info["compiles"]
    harness.log(f"set-up {info['setup_s']:.3f}s; phases {ph}; "
                f"{info['warm_calls']} warm-up calls; {info['ramp_turns']} "
                f"ramp turns; {info['kv_pages']} KV pages; "
                f"{c['programs']} programs compiled or loaded in "
                f"{c['seconds']:.3f}s, {c['cache_hits']} from the "
                f"persistent cache; compiled: {c['compiled']}")
    if info["compiled_in_window"]:
        harness.log(f"compiled or loaded after the window opened: "
                    f"{info['compiled_in_window']}")
    harness.log(f"reference: {info['readings']}")
    print(json.dumps({"samples": info["samples"],
                      "readings": info["readings"],
                      "engine": info["engine"]}), flush=True)
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        harness.log(f"check {name}={c['value']} limit={c['limit']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
