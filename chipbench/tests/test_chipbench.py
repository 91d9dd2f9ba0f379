"""CPU tests of the on-chip benchmark's harness (``chipbench``).

They check what needs no chip: finding cells, configurations,
architectures, mixes and readers by name; the dense architecture's weights,
reference and work counts against values pinned before it became a module
of its own; the traffic generator; the reduction of a trace; the end-to-end
statistics at the window's edges; a whole run of a tiny cell through the
server on the CPU, with its check passing, and failing when served tokens
are altered or when the int8 control stands in their place; a run through
an architecture the harness has never seen; and the refusal to run without
a TPU.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import re
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from chipbench import harness, roofline, spec, traffic
from chipbench import trace as trace_mod
from chipbench.arch import API as ARCH_API
from chipbench.arch import dense

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]

TINY_CONF = {
    "name": "tiny", "arch": "dense", "program_arch": "internlm2-1.8b",
    "frontend": "none",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "torch_dtype": "float32",
    "lora": {"rank": 4, "alpha": 8},
    "init": {"std": 0.02, "qk_std": 0.04, "lora_b_std": 0.04},
    "serve": {"mode": "forkkv", "page_size": 16, "max_batch": 4,
              "max_prefill_tokens": 32},
    "compare": {"min_tokens": 32, "max_requests": 3},
    "limits": {"mean_logit_gap": 0.001}}
TINY_MIX = {
    "name": "tiny-loop", "groups": 2, "agents_per_group": 2,
    "doc_tokens": 48, "doc_shared": True, "instruction_tokens": 4,
    "observation_tokens": 8,
    "output_tokens": {"median": 6, "sigma": 0.5, "min": 3, "max": 12},
    "stagger_first_turn": True, "turns_per_fork": 2, "rounds": 8}


# --------------------------------------------------------------- discovery
@pytest.mark.parametrize("cell", CELLS)
def test_cell_parts_are_found_by_name(cell):
    c = spec.load_cell(cell, BENCH)
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert c.config["name"] == entry["config"]
    assert c.traffic["name"] == entry["traffic"]
    names = [m.name for m in c.end_to_end]
    assert "setup_s" in names and len(names) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.load_reader(m.name))


CONFIGS = sorted(p.stem for p in (spec.PKG / "configs").glob("*.json"))


@pytest.mark.parametrize("conf", CONFIGS)
def test_config_file_states_its_source_and_cuts(conf):
    body = spec.load_config(conf)
    for key in body["reduced"]:
        assert body["published"][key] != body[key]
    assert body["rope_theta"] == 1e6 and body["frontend"] == "none"
    assert body["source"].startswith("https://huggingface.co/")
    entry = next((c for c in BENCH["configs"] if c["name"] == conf), None)
    if entry is not None:
        assert body["source"] == entry["source"]
        assert body["reduced"] == entry["reduced"]
        assert entry["file"] == f"chipbench/configs/{conf}.json"


def test_unknown_names_are_refused():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell", BENCH)
    with pytest.raises(spec.SpecError):
        spec.load_reader("../run")
    with pytest.raises(spec.SpecError):
        spec.load_traffic("missing-mix")


def test_a_missing_arch_is_refused_with_its_path(monkeypatch, tmp_path):
    conf = dict(spec.load_config("internlm2-1.8b"), arch="no-such-arch")
    monkeypatch.setattr(spec, "load_config", lambda name: conf)
    with pytest.raises(spec.SpecError, match=re.escape(
            str(spec.ARCHS / "no-such-arch.py"))):
        spec.load_cell(CELLS[0], BENCH)
    (tmp_path / "partial.py").write_text("def dims_of(conf):\n    pass\n")
    monkeypatch.setattr(spec, "ARCHS", tmp_path)
    with pytest.raises(spec.SpecError, match="lacks"):
        spec.load_arch("partial")


def test_metrics_with_a_workloads_list_go_to_those_cells_alone():
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append(dict(bench["workloads"][0], name="other"))
    first = bench["workloads"][0]["name"]
    bench["per_layer"][0]["workloads"] = ["other"]
    bench["per_layer"][1]["workloads"] = [first, "other"]
    listed = bench["per_layer"][0]["name"]
    for cell, has in ((first, False), ("other", True)):
        names = [m.name for m in spec.load_cell(cell, bench).per_layer]
        assert (listed in names) == has
        assert names[-1] == bench["per_layer"][-1]["name"]
        assert len(names) == len(bench["per_layer"]) - (not has)


# -------------------------------------------------------- the dense arch
def _control_conf():
    """A tiny model whose logits are large enough for int8 rounding to
    change the best token."""
    return dict(TINY_CONF, vocab_size=2048, hidden_size=128,
                intermediate_size=256,
                init={"std": 0.1, "qk_std": 0.1, "lora_b_std": 0.1})


def _control_request():
    rng = np.random.default_rng(0)
    doc = rng.integers(0, 2048, 32).tolist()
    prompt = doc + rng.integers(0, 2048, 8).tolist()
    served = rng.integers(0, 2048, 24).tolist()
    return doc, prompt, served


# what the dense decoder's code gave before it moved into chipbench/arch/
PINNED_WEIGHTS_SHA256 = \
    "33a660945f4f18f9d5397d5cf0224cda2bb72092e26cec37554fb860d6fddef2"
PINNED_GAPS = [
    1.6988797187805176, 3.463827133178711, 2.2221953868865967,
    3.323200225830078, 2.992680311203003, 3.0898003578186035,
    3.656269073486328, 3.229217529296875, 4.7586846351623535,
    4.6663641929626465, 3.1005611419677734, 4.793985366821289,
    4.161606788635254, 4.582139492034912, 3.4545063972473145,
    5.051834583282471, 6.001264572143555, 5.146727085113525,
    5.210063934326172, 2.46273136138916, 2.949583053588867,
    4.032718181610107, 2.7922897338867188, 4.720113754272461]
PINNED_CONTROL_GAPS = [0.0] * 22 + [0.03625011444091797, 0.0]
PINNED_ROWS = [roofline.Row(40, 1, (7, 8, 9, 100)),
               roofline.Row(16, 20, (7, 8, 10, 101))]
PINNED_LAYER_WORK = {"flops": 185600.0, "bytes": 16128.0}
PINNED_STEP_FLOPS = 3561984.0


def test_dense_arch_reads_what_it_read_before_it_moved():
    dims = dense.dims_of(TINY_CONF)
    params, lora = dense.make_weights(dims, 3, 2 ** 31 + 77)
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves((params, lora)):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == PINNED_WEIGHTS_SHA256
    cdims = dense.dims_of(_control_conf())
    cparams, clora = dense.make_weights(cdims, 3, 8)
    doc, prompt, served = _control_request()
    prog, ctrl = dense.gaps(cparams, clora, cdims, 2, prompt, served, doc,
                            128, 32, control=True)
    # to the last bits only: XLA's CPU matmuls may sum in another order on
    # another CPU
    np.testing.assert_allclose(prog, PINNED_GAPS, rtol=1e-6, atol=0)
    np.testing.assert_allclose(ctrl, PINNED_CONTROL_GAPS, rtol=1e-6,
                               atol=1e-7)
    bdims = _dims()
    for kind in ("decode", "mixed"):
        assert dense.grid_work(PINNED_ROWS, bdims, 16, kind) == {
            k: v * bdims.layers for k, v in PINNED_LAYER_WORK.items()}
    assert dense.step_flops(PINNED_ROWS, bdims) == PINNED_STEP_FLOPS


def test_mistral_model_config_carries_the_file_sizes():
    conf = spec.load_config("mistral-7b")
    cfg = dense.model_config(conf, dense.dims_of(conf))
    stated = dict(
        name="mistral-7b", family="dense", num_layers=16, d_model=4096,
        num_heads=32, num_kv_heads=8, head_dim=128, d_ff=14336,
        vocab_size=32768, rope_theta=1e6, norm_eps=1e-5, sliding_window=0,
        tie_embeddings=False, frontend="none", num_patches=0,
        dtype="bfloat16", mlp_activation="silu", kv_quant="none",
        num_experts=0)
    for key, value in stated.items():
        assert getattr(cfg, key) == value, key
    assert (cfg.lora.rank, cfg.lora.alpha, tuple(cfg.lora.targets)) == \
        (16, 32.0, ("q", "k", "v"))
    assert cfg.resolved_head_dim * cfg.num_heads == cfg.d_model
    # every other field as a plain dense decoder leaves it
    for f in dataclasses.fields(cfg):
        if f.name in stated or f.name in ("lora", "citation"):
            continue
        default = (f.default if f.default is not dataclasses.MISSING
                   else f.default_factory())
        assert getattr(cfg, f.name) == default, f.name


# ----------------------------------------------------------------- traffic
def test_seeds_deal_out_one_set_of_lengths_per_round():
    mix = dict(spec.load_traffic("doc-loop"), rounds=64,
               output_tokens={"median": 48, "sigma": 0.7, "min": 16,
                              "max": 256})
    a = traffic.Traffic(mix, 3, 1000)
    b = traffic.Traffic(mix, 2 ** 31 + 11, 1000)
    for k in range(mix["rounds"]):
        ra = sorted(ag.lengths[k] for ag in a.agents)
        assert ra == sorted(ag.lengths[k] for ag in b.agents)
        assert ra == sorted(traffic.round_lengths(
            mix["output_tokens"], len(a.agents), k))
    assert [x.lengths for x in a.agents] != [y.lengths for y in b.agents]
    every = [n for ag in a.agents for n in ag.lengths]
    assert min(every) == 16 and max(every) == 256
    assert 40 <= sorted(every)[len(every) // 2] <= 56
    again = traffic.Traffic(mix, 3, 1000)
    assert again.docs == a.docs
    assert [ag.next_turn().prompt for ag in again.agents] == \
        [ag.next_turn().prompt for ag in a.agents]


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_first_turns_are_staggered_then_full_length(seed):
    mix = spec.load_traffic("doc-loop")
    t = traffic.Traffic(mix, seed, 1000)
    first = [ag.next_turn().max_new for ag in t.agents]
    assert first == [16 * (i + 1) for i in range(16)]
    assert {ag.next_turn().max_new for ag in t.agents} == {256}
    assert {ag.next_turn().max_new for ag in t.agents} == {256}


def test_working_set_pages():
    # 2 documents of 3 pages; a branch reaches 2 x (12 + 12) = 48 tokens
    # past its document: 3 pages, and one it shares with the document
    assert traffic.working_set_pages(TINY_MIX, 16) == 2 * 3 + 4 * 4 + 1
    private = dict(TINY_MIX, doc_shared=False)
    assert traffic.working_set_pages(private, 16) == 4 * 3 + 4 * 4 + 1


def test_turns_fork_from_the_document_again():
    mix = dict(TINY_MIX, turns_per_fork=2)
    t = traffic.Traffic(mix, 5, 100)
    ag = t.agents[0]
    first = ag.next_turn()
    assert first.first_of_fork and first.prompt[:48] == t.docs[0]
    ag.finish_turn(first, [7] * first.max_new)
    second = ag.next_turn()
    assert not second.first_of_fork
    assert second.prompt[:len(first.prompt) + first.max_new] == \
        first.prompt + [7] * first.max_new
    ag.finish_turn(second, [1])
    third = ag.next_turn()
    assert third.first_of_fork and len(third.prompt) == 48 + 12
    assert traffic.longest_context(mix) == 48 + 2 * (12 + 12)


# ------------------------------------------------------------ roofline
def _dims(**kw):
    conf = dict(TINY_CONF, torch_dtype="bfloat16", **kw)
    return dense.dims_of(conf)


def test_grid_work_counts_shared_pages_once():
    d = _dims()        # 2 layers; 4 q heads, 2 kv heads of 16, rank 4, bf16
    page = 16
    # two decode rows over the same 2 pages + one private page each
    rows = [roofline.Row(40, 1, (7, 8, 9, 100)),
            roofline.Row(40, 1, (7, 8, 10, 101))]
    w = dense.grid_work(rows, d, page, "decode")
    unique = 4                                     # pages 7, 8, 9, 10
    base = unique * 2 * 2 * page * 16 * 2          # k,v x kv heads x D x 2B
    res = 6 * 2 * page * 4 * 2                     # 3 pages a row, rank 4
    qo = 2 * 2 * 4 * 16 * 2                        # q and out, 2 tokens
    ada = 2 * 2 * 4 * 32 * 2                       # B_k, B_v per row
    assert w["bytes"] == 2 * (base + res + qo + ada)
    attn = 2 * 41                                  # each query sees 41
    assert w["flops"] == 2 * (4 * 4 * 16 * attn + 4 * 4 * 32 * 2 * 41)


def test_grid_work_mixed_row_is_causal():
    d = _dims()
    w = dense.grid_work([roofline.Row(16, 4, (1, 2))], d, 16, "mixed")
    ctx = 17 + 18 + 19 + 20
    assert w["flops"] == 2 * (4 * 4 * 16 * ctx + 4 * 4 * 32 * 20)
    one = dense.step_flops([roofline.Row(16, 4, (1, 2))], d)
    proj = 64 * (2 * 64 + 2 * 32) + 3 * 64 * 128
    lora = 3 * 64 * 4 + 4 * (64 + 64)
    assert one == 2 * (2 * (proj + lora) * 4 + 4 * 4 * 16 * ctx) + \
        2 * 64 * 256


def test_roofline_share_and_peaks():
    p = roofline.peaks("TPU v5 lite")
    assert roofline.roofline_share(197e12, 0, 2.0, p) == pytest.approx(50)
    assert roofline.roofline_share(0, 819e9, 4.0, p) == pytest.approx(25)
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


# ------------------------------------------------------------------- trace
def _ev(name, start_us, dur_us):
    return trace_mod.Event(name, start_us * 1e3, dur_us * 1e3)


def test_trace_reduction():
    spans = [_ev("bench.window", 100, 1000),
             _ev("bench.poll", 100, 500), _ev("bench.exec.decode", 120, 30),
             _ev("bench.poll", 600, 500), _ev("bench.submit", 1050, 20)]
    ops = [_ev("%paged_residual_attention_decode.3 = bf16[16] custom-call",
               50, 150),                           # clipped to 100..200
           _ev("fusion.12", 200, 100), _ev("fusion.7.remat2", 250, 100),
           _ev("paged_residual_attention_mixed.1", 700, 300),
           _ev("copy", 2000, 10)]                  # outside the window
    modules = [_ev("jit__decode_fn(3)", 100, 250),
               _ev("jit__prefill_fn(9)", 700, 300)]
    s = trace_mod.reduce([(ops, modules)], spans)
    assert s.window_s == pytest.approx(1e-3)
    assert s.busy_s == pytest.approx(550e-6)       # 100..350, 700..1000
    assert s.op_s["paged_residual_attention_decode"] == pytest.approx(1e-4)
    assert s.op_s["fusion"] == pytest.approx(2e-4)
    assert s.module_s["jit__decode_fn(3)"] == [pytest.approx(250e-6)]
    # idle 350..700: poll (mid 525); 1000..1100: submit at 1050
    assert s.idle_gaps["bench.poll"] == pytest.approx(350e-6)
    assert s.idle_gaps["bench.submit"] == pytest.approx(100e-6)
    assert trace_mod.reduce([(ops, modules)], spans[1:]) is None
    rows = trace_mod.top(s.op_s, 2, harness.GRIDS)
    assert [r[0] for r in rows] == ["paged_residual_attention_mixed",
                                    "paged_residual_attention_decode"]


# ----------------------------------------------------- end-to-end numbers
def _run(records, t0=10.0, t1=20.0, **kw):
    d = _dims()
    base = dict(t0=t0, t1=t1, records=records, setup_s=5.0, phases={},
                arch=dense, dims=d, page=16, kv_pages=100, kv_peak_share=0.25,
                counters=({"prefilled": 0, "prompt": 0},
                          {"prefilled": 50, "prompt": 200}),
                exec_calls=[])
    base.update(kw)
    return harness.Run(**base)


def _rec(submit, receipts):
    r = harness.TurnRecord(0, 1, [1, 2], len(receipts), submit)
    r.receipts = list(receipts)
    r.tokens = [0] * len(receipts)
    return r


def test_window_edges():
    recs = [_rec(8.0, [9.0, 9.5, 10.5, 11.0]),    # gap 9.5->10.5 counts
            _rec(12.0, [12.25, 12.75]),
            _rec(19.5, [20.5, 21.0]),              # submitted in, first out
            _rec(20.0, [20.2])]                    # submitted at the end
    run = _run(recs)
    assert run.tokens() == 4
    assert sorted(run.gaps_s()) == [0.5, 0.5, 1.0]
    assert sorted(run.ttfts_s()) == [0.25, 1.0]
    read = spec.load_reader
    assert read("output_tokens_per_s")(run) == pytest.approx(0.4)
    assert read("itl_p99_ms")(run) == pytest.approx(
        1e3 * np.percentile([0.5, 0.5, 1.0], 99))
    assert read("setup_s")(run) == 5.0


def test_engine_and_pool_readers():
    run = _run([])
    read = spec.load_reader
    assert read("engine.prefill_saved_share")(run) == pytest.approx(75.0)
    assert read("kv.peak_pages_share")(run) == pytest.approx(25.0)
    for name in ("executor.decode_step_ms", "kernel.decode_roofline",
                 "step_mfu", "device.idle_share"):
        assert read(name)(run) is None             # nothing traced


def test_trace_readers():
    rows = (roofline.Row(100, 1, tuple(range(8))),)
    summ = trace_mod.Summary(
        window_s=2.0, busy_s=1.5,
        op_s={"paged_residual_attention_decode": 0.5},
        module_s={"jit__decode_fn": [0.1, 0.3], "jit__prefill_fn": [0.4]},
        idle_gaps={}, devices=1)
    run = _run([], exec_calls=[(11.0, "decode", rows)],
               trace=summ, peak=roofline.peaks("TPU v5 lite"))
    read = spec.load_reader
    assert read("device.idle_share")(run) == pytest.approx(25.0)
    assert read("executor.decode_step_ms")(run) == pytest.approx(200.0)
    assert read("executor.mixed_step_ms")(run) == pytest.approx(400.0)
    w = dense.grid_work(rows, run.dims, 16, "decode")
    assert read("kernel.decode_roofline")(run) == pytest.approx(
        roofline.roofline_share(w["flops"], w["bytes"], 0.5, run.peak))
    assert read("kernel.mixed_roofline")(run) is None
    assert read("step_mfu")(run) == pytest.approx(
        100 * dense.step_flops(rows, run.dims) / 2.0 / 197e12)


# ----------------------------------------------------- a tiny cell, on CPU
def _tiny_cell(conf=TINY_CONF, mix=TINY_MIX):
    return spec.Cell("tiny.tiny-loop", 1, conf, mix,
                     spec._metrics(BENCH, "end_to_end"),
                     spec._metrics(BENCH, "per_layer"),
                     spec.load_arch(conf["arch"]))


def test_tiny_cell_runs_and_passes_its_check():
    res = harness.run_cell(_tiny_cell(), 2 ** 31 + 77, 2.0, False,
                           time.perf_counter(), jax.devices()[0])
    log = res.pop("_log")
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert log["readings"]["compared_tokens"] >= 10
    assert log["readings"]["max_logit_gap"] < 1e-4
    assert log["readings"]["mean_logit_gap"] < 1e-5
    assert set(res["metrics"]) == {m["name"] for m in BENCH["end_to_end"]}
    assert res["attempted"] > 0 and res["failed"] == 0
    # the pool holds the mix's working set: nothing is preempted
    assert log["kv_pages"] == 64 and log["engine"]["preempted"] == 0


def test_tiny_cell_fails_when_a_served_token_is_altered(monkeypatch):
    from repro.serving.executor import PagedExecutor
    inner = PagedExecutor.mixed_step

    def altered(self, *a, **kw):
        out = inner(self, *a, **kw)
        return ((out[0] + 1) % self.cfg.vocab_size,) + tuple(out[1:])
    monkeypatch.setattr(PagedExecutor, "mixed_step", altered)
    res = harness.run_cell(_tiny_cell(), 4, 1.0, False, time.perf_counter(),
                           jax.devices()[0])
    assert not res["correct"]
    assert res["checks"]["mean_logit_gap"]["value"] > 0.001


def test_control_reads_above_the_program():
    """The int8 control on a tiny model whose logits are large enough for
    int8 rounding to change the best token."""
    dims = dense.dims_of(_control_conf())
    params, lora = dense.make_weights(dims, 3, 8)
    doc, prompt, served = _control_request()
    prog, ctrl = dense.gaps(params, lora, dims, 2, prompt, served, doc,
                            128, 32, control=True)
    same, _ = dense.gaps(params, lora, dims, 2, prompt, served, doc, 128,
                         32)
    np.testing.assert_allclose(prog, same, rtol=1e-6, atol=1e-6)
    assert (prog >= 0).all() and (ctrl >= 0).all()
    assert ctrl.max() > 0.0


def test_tiny_cell_fails_under_the_int8_control():
    """The int8 control's picks in the served tokens' place fail the
    check that the served tokens pass, on a tiny model whose logits are
    large enough for int8 rounding to change the best token."""
    res = harness.run_cell(_tiny_cell(_control_conf()), 9, 1.0, False,
                           time.perf_counter(), jax.devices()[0],
                           control=True)
    readings = res.pop("_log")["readings"]
    assert readings["mean_logit_gap"] < 1e-5
    assert not res["correct"]
    gap = res["checks"]["mean_logit_gap"]
    assert gap["value"] == readings["control_mean_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_a_new_arch_runs_with_no_edit_to_the_harness(monkeypatch):
    """An architecture the harness has never seen, found where ``spec``
    looks for architectures: a traced run of the tiny cell reaches the
    model through its functions alone, and passes its check."""
    monkeypatch.setattr(spec, "ARCHS", spec.PKG / "tests" / "archs")
    arch = spec.load_arch("recording")
    arch.CALLS.clear()
    # a CPU run records no device trace: a summary of one and the v5e's
    # peaks stand in, so that the readers counting the work run
    summary = trace_mod.Summary(
        window_s=2.0, busy_s=1.0, op_s={g: 0.1 for g in harness.GRIDS},
        module_s={}, idle_gaps={}, devices=1)
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(trace_mod, "load", lambda log_dir: ([], []))
    monkeypatch.setattr(trace_mod, "reduce", lambda devices, spans: summary)
    monkeypatch.setattr(roofline, "peaks",
                        lambda kind: roofline.PEAKS["TPU v5 lite"])
    res = harness.run_cell(_tiny_cell(dict(TINY_CONF, arch="recording")),
                           2 ** 31 + 78, 2.0, True, time.perf_counter(),
                           jax.devices()[0])
    log = res.pop("_log")
    assert res["correct"], res["checks"]
    assert log["readings"]["compared_tokens"] >= 10
    assert set(arch.CALLS) == set(ARCH_API)
    for name in ("kernel.decode_roofline", "step_mfu"):
        assert res["metrics"][name]["value"] > 0


# ------------------------------------------------------------- no chip
def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 3
    assert "correct" not in p.stdout
    assert "TPU" in p.stderr


def test_benchmark_file_keeps_to_its_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert json.dumps(BENCH).__len__() < 64 * 1024
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] == 1
    for c in BENCH["configs"]:
        assert len(c["why"]) <= 200
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_what_its_layers_move(cell):
    c = spec.load_cell(cell, BENCH)
    e2e = {m.name for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    for m in c.per_layer:
        assert m.moves in e2e, (cell, m.name, m.moves)
