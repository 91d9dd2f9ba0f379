#!/usr/bin/env python3
"""Smoke run of the ForkKV serving path on one TPU chip.

    python3 chip_smoke.py                  # on a TPU host (exits 1 elsewhere)
    python3 chip_smoke.py --cpu-rehearsal  # serve-tiny on the CPU, Pallas
                                           # kernels in interpret mode

Drives the path a user calls — ``build_server`` -> ``ForkServer`` ->
``Engine`` -> ``PagedExecutor`` -> the paged Pallas grids — in one process,
on Llama3-8B at its published widths cut to 8 of 32 layers
(``configs/paper_models.llama3_8b_stage``), random bf16 weights from
``--seed``, 32 rank-16 adapters and a KV pool of 4096 pages:

1. ``forkkv``: one probe request alone, then a session on a 2,048-token
   shared context (adapter 0) and 4 forked agents (adapters 1-4), each with
   a 64-token instruction and 32 greedy new tokens;
2. ``prefix``: the same probe and traffic on the same weights (the
   base-only grids);
3. ``attention``: in each mode, every paged grid the mode serves (decode,
   chunked prefill, mixed) is run on the probe's served KV pages, layer by
   layer, with the Pallas kernels in bf16 and compared with the XLA
   mirror in float32 at ``highest`` matmul precision on the same inputs:
   within ``ATTN_RTOL``;
4. ``logits``: the probe's first decode step in each mode, served again
   through the XLA mirror (``kernel_ops.set_backend("ref")``): bf16
   Pallas vs mirror within ``BF16_LOGITS_RTOL``; then with the weights cast
   to float32 at ``highest`` precision through both: within ``F32_RTOL``.

It exits 1, and prints no result, when the device is not a TPU, when the
kernels would run through the XLA mirror or in interpret mode, on any
executor error, quarantined row or gather fallback, on a request that ends
in error or stalls, and when a grid's output or the logits disagree.  The
compile seconds, time to first token, tokens/s and peak HBM it prints are
smoke readings of one cold run, not benchmarks.  The last line of standard output is
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Relative L2 distances (rel_l2) allowed.  F32_RTOL: the probe's first
# decode-step logits, Pallas vs the XLA mirror, weights in float32 at
# "highest" matmul precision.  The two compute the same function and
# differ by summation order only (1.9e-5 through 8 layers, PERF.md); any
# bf16 rounding on either side moves them by ~1e-2.
F32_RTOL = 1e-3
# ATTN_RTOL: one paged grid's bf16 output on served pages vs the XLA
# mirror in float32 on the same bf16 inputs.  Rounding here is one layer's
# (the output and the probabilities in bf16), not the model's, so a fault
# of a few percent in one grid stands out.
ATTN_RTOL = 1e-2
# BF16_LOGITS_RTOL: bf16 logits, Pallas vs mirror.  bf16 rounding through
# the 8 random layers moves either path ~3% from float32, so this bound
# only catches gross faults; ATTN_RTOL and F32_RTOL are the tight checks.
BF16_LOGITS_RTOL = 6e-2
# q rows of the chunked-prefill and mixed grid checks (the served chunk)
GRID_CHUNK = 128

TRAFFIC = {
    # model: shared context, instruction, new tokens, agents, probe prompt
    "llama3-8b": dict(context=2048, instr=64, new=32, agents=4, probe=200),
    "serve-tiny": dict(context=192, instr=16, new=8, agents=4, probe=40),
}
SERVER = {
    "llama3-8b": dict(max_pages=4096, max_pages_per_req=256, max_batch=8,
                      n_adapters=32),
    "serve-tiny": dict(max_pages=256, max_pages_per_req=24, max_batch=8,
                       n_adapters=32),
}
PROBE_ADAPTER = 5


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _compile_clock():
    """Seconds JAX spent compiling programs (or loading them from the
    persistent cache) and their number, summed from its monitoring
    events.  Tracing is left out: the kernels' own jits are traced inside
    each step's trace, so summed trace events would count them twice."""
    import jax
    total = {"s": 0.0, "programs": 0}

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total["s"] += duration
            total["programs"] += 1
    jax.monitoring.register_event_duration_secs_listener(listener)
    return total


def _peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", -1), stats.get("bytes_limit", -1)


def _probe(server, prompt):
    """Serve ``prompt`` alone.  Returns the logits row of its first decode
    step and what that step read: the request's KV pages copied out of
    the served pools (``_served_pages``), its kv_len and adapter."""
    import numpy as np
    from repro.serving.sampling import SamplingParams
    ex = server.engine.executor
    seen = []
    decode = ex.decode

    def spy(tokens, kv_len, adapter_ids, base_tables, res_tables, *a, **kw):
        if not seen:
            seen.append((_served_pages(ex, base_tables[0], res_tables[0]),
                         kv_len[0], adapter_ids[0]))
        out = decode(tokens, kv_len, adapter_ids, base_tables, res_tables,
                     *a, **kw)
        if len(seen) == 1:
            seen.append(np.asarray(out[1][0], np.float32))
        return out
    ex.decode = spy
    out = server.generate(PROBE_ADAPTER, prompt,
                          SamplingParams(max_new_tokens=2)).result()
    ex.decode = decode
    check(out.finish_reason == "length" and not out.error,
          f"probe request ended {out.finish_reason}: {out.error}")
    check(len(seen) == 2, "probe request took no decode step")
    return seen[1], seen[0]


def _served_pages(ex, bt_b, bt_r):
    """One request's KV pages, every layer, copied out of the executor's
    pools as it wrote them, into compact pools whose page ``i`` is the
    request's ``i``-th page."""
    import jax.numpy as jnp
    from repro.kernels import paged_residual_attention as pra
    p = ex.pools
    ids = jnp.asarray(bt_b, jnp.int32)
    if p.kr is None:
        return p.kb[:, ids], p.vb[:, ids], None, None
    r, rid = ex.cfg.lora.rank, jnp.asarray(bt_r, jnp.int32)
    return (p.kb[:, ids], p.vb[:, ids],
            pra.to_res_pool(pra.res_pages(p.kr, rid, r)),
            pra.to_res_pool(pra.res_pages(p.vr, rid, r)))


def attention_errors(cfg, lora, probe, seed):
    """Run every paged grid (decode, chunked prefill, mixed) on the
    probe's served pages, layer by layer: the Pallas kernels and the XLA
    mirror in the pages' dtype, and the mirror in float32 at ``highest``
    precision on the same inputs.  The queries are random, from ``seed``;
    the base-only grids run when the pages hold no residual.  Returns
    {grid: (worst Pallas rel_l2, worst mirror rel_l2)} over the layers,
    both against the float32 mirror."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    (kb, vb, kr, vr), kv_len, adapter = probe
    hq, d, dt = cfg.num_heads, cfg.resolved_head_dim, kb.dtype
    chunk = min(GRID_CHUNK, kv_len)
    bt = jnp.arange(kb.shape[1], dtype=jnp.int32)[None]
    kvl = jnp.asarray([kv_len], jnp.int32)
    start, q_len = kvl - chunk, jnp.asarray([chunk], jnp.int32)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 2))
    q1 = jax.random.normal(k1, (1, hq, d), dt)
    qc = jax.random.normal(k2, (1, chunk, hq, d), dt)
    kw = dict(scale=d ** -0.5, window=cfg.sliding_window,
              rope_theta=cfg.rope_theta, use_rope=cfg.use_rope)
    grids = {
        "decode": lambda q, a, be: ops.paged_residual_attention(
            q[0], *a, kvl, backend=be, **kw),
        "prefill": lambda q, a, be: ops.paged_residual_attention_prefill(
            q[1], *a, start, kvl, backend=be, **kw),
        "mixed": lambda q, a, be: ops.paged_residual_attention_mixed(
            q[1], *a, start, q_len, kvl, backend=be, **kw),
    }
    grids = {g: jax.jit(f, static_argnums=2) for g, f in grids.items()}

    def f32(tree):
        return jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)
    worst = {g: [0.0, 0.0] for g in grids}
    for li in range(kb.shape[0]):
        pools = [None if x is None else x[li:li + 1] for x in (kb, vb, kr,
                                                             vr)]
        if kr is None:
            args = (*pools, None, None, bt, None)
        else:
            bk = lora["b_k"][li, adapter][None]
            bv = lora["b_v"][li, adapter][None]
            args = (*pools, bk, bv, bt, bt)
        for g, run in grids.items():
            with jax.default_matmul_precision("highest"):
                truth = np.asarray(run(f32((q1, qc)), f32(args), "ref"))
            for k, be in enumerate(("pallas", "ref")):
                got = np.asarray(run((q1, qc), args, be), np.float32)
                worst[g][k] = max(worst[g][k], rel_l2(got, truth))
    return worst


def _check_metrics(mode, m, outs, new):
    for o in outs:        # first: a failed request carries the error text
        check(o.finish_reason in ("length", "stop") and not o.error,
              f"{mode}: request {o.rid} ended {o.finish_reason}: {o.error}")
        check(o.finish_reason == "stop" or len(o.tokens) == new,
              f"{mode}: request {o.rid} made {len(o.tokens)} of {new} tokens")
    for key in ("exec_errors", "quarantined", "fallback_gather_calls",
                "stalled"):
        check(m[key] == 0, f"{mode}: {key}={m[key]}")


def run_mode(mode, model, weights, prompts, probe_prompt, seed, clock, dev):
    """One server in ``mode``: the probe alone, then the session/fork
    traffic ``prompts = (context, instructions, new tokens)``, then the
    attention check on the probe's pages.  Returns (probe logits, the
    attention check's readings, pool bytes)."""
    ctx, instrs, new = prompts
    from repro.launch.serve import build_server
    from repro.serving.executor import pool_bytes
    from repro.serving.sampling import SamplingParams
    c0, t0 = dict(clock), time.perf_counter()
    server, cfg = build_server(mode, model=model, params=weights[0],
                               lora=weights[1], seed=seed, **SERVER[model])
    pools = sum(pool_bytes(server.engine.executor.pools).values())
    logits, probe = _probe(server, probe_prompt)
    sess = server.session(ctx, adapter_id=0)
    t_fork = time.perf_counter()
    handles = [sess.fork(i + 1, instr, SamplingParams(max_new_tokens=new))
               for i, instr in enumerate(instrs)]
    outs = server.wait(handles)
    wall = time.perf_counter() - t_fork
    sess.close()
    m = server.metrics()
    _check_metrics(mode, m, outs, new)
    if mode == "forkkv":
        check(m["hit_tokens"] > 0, "forkkv: forks did not reuse the context")
    ttft = sorted(o.metrics["ttft_ms"] for o in outs)
    gen = sum(len(o.tokens) for o in outs)
    peak, limit = _peak_bytes(dev)
    print(f"smoke reading, not a benchmark: mode={mode} "
          f"backend_compile_s={clock['s'] - c0['s']:.1f} "
          f"programs={clock['programs'] - c0['programs']} "
          f"phase_s={time.perf_counter() - t0:.1f} "
          f"ttft_ms_min={ttft[0]:.1f} ttft_ms_max={ttft[-1]:.1f} "
          f"fork_tokens_per_s={gen / wall:.1f} "
          f"pool_bytes={pools} peak_bytes_in_use={peak} "
          f"bytes_limit={limit} hit_tokens={m['hit_tokens']}", flush=True)
    attn = attention_errors(cfg, weights[1], probe, seed)
    grids = "base-only" if probe[0][2] is None else "disaggregated"
    print(f"attention, {mode} ({grids} grids, {cfg.dtype}, worst of "
          f"{cfg.num_layers} layers, rel_l2 vs the float32 mirror): "
          + " ".join(f"{g}: pallas={e[0]:.3e} mirror={e[1]:.3e}"
                     for g, e in attn.items()), flush=True)
    return logits, attn, pools


def probe_logits(mode, model, weights, prompt, seed):
    """First-decode-step logits of ``prompt`` on a fresh ``mode`` server
    with a small pool, since only the probe runs."""
    from repro.launch.serve import build_server
    server, _ = build_server(mode, model=model, params=weights[0],
                             lora=weights[1], seed=seed,
                             **dict(SERVER[model], max_pages=64,
                                    max_pages_per_req=32))
    logits, _ = _probe(server, prompt)
    del server
    release()
    return logits


def to_f32(tree):
    """``tree`` cast to float32 leaf by leaf, each bf16 leaf freed as soon
    as its copy exists: whole, the bf16 and f32 weights would not fit one
    chip together."""
    import jax
    import jax.numpy as jnp
    flat, tdef = jax.tree_util.tree_flatten(tree)
    for i, x in enumerate(flat):
        flat[i] = x.astype(jnp.float32).block_until_ready()
        if flat[i] is not x:
            x.delete()
    return jax.tree_util.tree_unflatten(tdef, flat)


def rel_l2(a, b):
    import numpy as np
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def release():
    """Free a finished server's device pools before the next one is built
    (its engine holds reference cycles, so dropping the name is not
    enough)."""
    gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="serve-tiny on the CPU with the Pallas kernels in "
                         "interpret mode (a rehearsal, never a chip result)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"jax {jax.__version__} platform={dev.platform} "
          f"kind={dev.device_kind} count={device['count']}", flush=True)
    if not args.cpu_rehearsal and dev.platform != "tpu":
        print(f"FAIL: no TPU (platform {dev.platform})", file=sys.stderr)
        return 1

    import numpy as np
    from repro.configs.paper_models import SERVE_MODELS
    from repro.kernels import ops as kernel_ops
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import transformer as tfm

    model = "serve-tiny" if args.cpu_rehearsal else "llama3-8b"
    traffic = TRAFFIC[model]
    clock = _compile_clock()
    print(f"compile cache: {use_compile_cache()}", flush=True)
    if args.cpu_rehearsal:
        kernel_ops.set_backend("pallas-interpret")
    try:
        check(kernel_ops.get_backend() == "pallas",
              f"kernel backend is {kernel_ops.get_backend()!r}, not pallas")
        check(args.cpu_rehearsal or not kernel_ops.interpret_mode(),
              "Pallas kernels would run in interpret mode on the chip")

        cfg = SERVE_MODELS[model]()
        t0 = time.perf_counter()
        params = jax.jit(tfm.init_params, static_argnums=0)(
            cfg, jax.random.PRNGKey(args.seed))
        jax.block_until_ready(params)
        weight_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
        print(f"model {cfg.name}: {cfg.num_layers} layers, d_model "
              f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}, "
              f"vocab {cfg.vocab_size}, {cfg.dtype}; weights {weight_bytes} "
              f"bytes in {time.perf_counter() - t0:.1f}s", flush=True)

        rng = np.random.default_rng(args.seed)

        def tokens(n):
            return [int(t) for t in rng.integers(0, cfg.vocab_size, n)]
        probe = tokens(traffic["probe"])
        instrs = [tokens(traffic["instr"]) for _ in range(traffic["agents"])]
        prompts = (tokens(traffic["context"]), instrs, traffic["new"])
        lora = tfm.init_lora_stacks(cfg, jax.random.PRNGKey(args.seed + 1),
                                    n_adapters=SERVER[model]["n_adapters"])
        weights = [params, lora]
        del params
        modes = ("forkkv", "prefix")
        bf16, attn = {}, {}
        for mode in modes:
            bf16[mode], attn[mode], pools = run_mode(
                mode, model, weights, prompts, probe, args.seed, clock, dev)
            release()
            peak, limit = _peak_bytes(dev)
            if mode == "forkkv" and limit > 0:
                share = pools / (limit - weight_bytes)
                print(f"forkkv pools hold {share:.3f} of the HBM left after "
                      f"the weights", flush=True)
                check(share >= 1 / 3,
                      f"pools hold only {share:.3f} of free HBM")

        pallas = kernel_ops.get_backend()
        if args.cpu_rehearsal:
            pallas = "pallas-interpret"
        kernel_ops.set_backend("ref")
        mirror = {m: probe_logits(m, model, weights, probe, args.seed)
                  for m in modes}
        # the float32 model: the same weights and adapters, cast in place
        weights = [to_f32(w) for w in weights]
        truth, f32 = {}, {}
        with jax.default_matmul_precision("highest"):
            for m in modes:
                truth[m] = probe_logits(m, model, weights, probe, args.seed)
            kernel_ops.set_backend(pallas)
            for m in modes:
                f32[m] = probe_logits(m, model, weights, probe, args.seed)
        errs = {m: (rel_l2(f32[m], truth[m]), rel_l2(bf16[m], mirror[m]))
                for m in modes}
        for m, (f32_err, bf16_err) in errs.items():
            top1 = int(np.argmax(bf16[m])) == int(np.argmax(mirror[m]))
            print(f"logits, {m}: float32 pallas vs XLA mirror rel_l2="
                  f"{f32_err:.3e} (tolerance {F32_RTOL}); bf16 pallas vs "
                  f"mirror rel_l2={bf16_err:.3e} (tolerance "
                  f"{BF16_LOGITS_RTOL}), same_top1={top1}; bf16 vs float32: "
                  f"pallas {rel_l2(bf16[m], truth[m]):.3e} mirror "
                  f"{rel_l2(mirror[m], truth[m]):.3e}", flush=True)
        for m, (f32_err, bf16_err) in errs.items():
            check(np.isfinite(bf16[m]).all(), f"{m}: non-finite logits")
            check(f32_err <= F32_RTOL,
                  f"{m}: float32 logits differ, rel_l2 {f32_err:.3e}")
            check(bf16_err <= BF16_LOGITS_RTOL,
                  f"{m}: bf16 logits differ, rel_l2 {bf16_err:.3e}")
            for g, (err, _) in attn[m].items():
                check(err <= ATTN_RTOL,
                      f"{m}: {g} grid output off by rel_l2 {err:.3e}")
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    peak, _ = _peak_bytes(dev)
    print(f"smoke reading, not a benchmark: total backend_compile_s="
          f"{clock['s']:.1f} "
          f"programs={clock['programs']} peak_bytes_in_use={peak} (with the "
          f"float32 probe)", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
