#!/usr/bin/env bash
# Smoke check: tier-1 test suite + one tiny tiered-engine workflow
# end-to-end (HBM→host demotion under pressure, DESIGN.md §10) + the
# session/fork API example in all three cache-sharing modes (§11).
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1 tests =="
python -m pytest -x -q

echo "== tiered-engine workflow e2e =="
python - <<'PY'
import jax
from repro.configs.paper_models import tiny_serving_model
from repro.core.config import ServeConfig
from repro.models import transformer as tfm
from repro.serving.api import ForkServer
from repro.serving.workflows import WorkflowConfig, WorkflowDriver

cfg = tiny_serving_model(rank=8)
params = tfm.init_params(cfg, jax.random.PRNGKey(0))
lora = tfm.init_lora_stacks(cfg, jax.random.PRNGKey(1), n_adapters=8)
sc = ServeConfig(page_size=16, max_pages=26, max_batch=4,
                 max_prefill_tokens=64, mode="forkkv",
                 max_pages_per_req=24, host_tier_bytes=64 << 20)
server = ForkServer(cfg, params, lora, sc)
wf = WorkflowConfig(n_workflows=3, agents_per_workflow=2, rounds=2,
                    shared_context_len=256, instr_len=16, tool_obs_len=24,
                    max_new_tokens=4, vocab=cfg.vocab_size, seed=0)
rep = WorkflowDriver(server, wf).run_react()
assert rep["tasks_done"] == 12, rep["tasks_done"]
assert rep["demoted_pages"] > 0, "expected demotions under pressure"
assert rep["tier_hits"] > 0, "expected host-tier promotions"
eng = server.engine
assert eng.base_pool.free_pages + eng.base_pool.used_pages == 26
print(f"tiered e2e OK: tasks={rep['tasks_done']} "
      f"tier_hits={rep['tier_hits']} demoted={rep['demoted_pages']} "
      f"promoted_bytes={rep['promoted_bytes']} "
      f"prefill_saved={rep['prefill_saved_frac']:.3f}")
PY

echo "== session/fork API example, all three modes =="
for mode in forkkv prefix full_reuse; do
  python examples/react_agent_tree.py --mode "$mode" --temperature 0.8
done

echo "== decode-step benchmark smoke (paged vs gather, DESIGN.md §12) =="
python -m benchmarks.bench_decode --smoke --out BENCH_decode.smoke.json
test -s BENCH_decode.smoke.json
python - <<'PY'
import json
rep = json.load(open("BENCH_decode.smoke.json"))
assert rep["rows"], "empty benchmark report"
assert all(r["us_per_decode_step"] > 0 for r in rep["rows"])
print("bench smoke OK:", rep["summary"])
PY

echo "== prefill benchmark smoke (page-native vs gather, DESIGN.md §13) =="
python -m benchmarks.bench_prefill --smoke --out BENCH_prefill.smoke.json
test -s BENCH_prefill.smoke.json
python - <<'PY'
import json
rep = json.load(open("BENCH_prefill.smoke.json"))
assert rep["rows"], "empty benchmark report"
assert all(r["us_per_prompt_token"] > 0 for r in rep["rows"])
assert all(r["fallback_gather_calls"] == 0 for r in rep["rows"]
           if r["path"] == "paged"), "paged prefill fell back to gather"
print("prefill bench smoke OK:", rep["summary"])
PY

echo "== serving benchmark smoke (mixed vs phase-separated, DESIGN.md §14) =="
python -m benchmarks.bench_serving --smoke --speculate \
  --out BENCH_serving.smoke.json
test -s BENCH_serving.smoke.json
python - <<'PY'
import json
rep = json.load(open("BENCH_serving.smoke.json"))
for side in ("mixed", "phase_separated"):
    s = rep[side]
    assert s["requests"] > 0 and s["gen_tokens"] > 0, s
    assert s["ttft_p99_ms"] > 0 and s["tpot_p99_ms"] > 0, s
    assert s["fallback_gather_calls"] == 0, s
assert rep["mixed"]["mixed_steps"] > 0, "no mixed iterations exercised"
assert rep["phase_separated"]["mixed_steps"] == 0
assert rep["comparison"]["throughput_ratio"] > 0
print("serving bench smoke OK:", rep["comparison"],
      "verdict:", rep["verdict"])
# speculative block (DESIGN.md §16): the repetitive trace must really
# speculate — drafts proposed AND accepted — with zero gather fallbacks
spec = rep["speculative"]
assert spec["speculate"]["spec_proposed_tokens"] > 0, spec
assert spec["speculate"]["spec_accepted_tokens"] > 0, spec
assert spec["comparison"]["acceptance_rate"] > 0, spec
assert spec["speculate"]["fallback_gather_calls"] == 0, spec
assert spec["baseline"]["spec_steps"] == 0
print("speculative bench smoke OK:", spec["comparison"],
      "verdict:", spec["verdict"])
PY

echo "== HTTP frontend smoke (SSE streaming + fork parity, DESIGN.md §15) =="
python -m repro.launch.serve --http --port 0 --max-pages 256 \
  --admission fairshare --speculate --spec-k 3 --proposer ngram_cache \
  > /tmp/forkkv_http.log 2>&1 &
HTTP_PID=$!
trap 'kill $HTTP_PID 2>/dev/null || true' EXIT
for _ in $(seq 120); do
  grep -q "on http://" /tmp/forkkv_http.log && break
  sleep 1
done
HTTP_PORT=$(sed -n 's#.*on http://[^:]*:\([0-9]*\).*#\1#p' /tmp/forkkv_http.log)
test -n "$HTTP_PORT" || { cat /tmp/forkkv_http.log; exit 1; }
HTTP_PORT="$HTTP_PORT" python - <<'PY'
import os
import numpy as np
from repro.serving.frontend import ForkClient

client = ForkClient(port=int(os.environ["HTTP_PORT"]))
assert client.healthz()
rng = np.random.default_rng(0)
ctx = [int(t) for t in rng.integers(0, 1000, 96)]
instr = ctx[:8]   # re-quotes the context, so the proposer has material

# streamed SSE completions through a forked session, SPECULATION ON
# (--speculate on the server); the identical second fork replays the
# first's trajectory out of the warmed ngram cache
sid = client.create_session(ctx, adapter_id=0)
runs = []
for _ in range(2):
    events = list(client.stream_fork(sid, instr, adapter_id=1,
                                     max_new_tokens=8))
    streamed = [e["token"] for e in events if not e.get("finished")]
    assert events[-1]["finished"] and len(streamed) == 8, events[-1]
    assert streamed == events[-1]["tokens"]
    runs.append(streamed)
assert runs[0] == runs[1], runs

# ...must match a speculation-OFF fork of the same session on the same
# server token-for-token (greedy ON==OFF parity; only the server process
# touches JAX), with the paged path never falling back to gather
expected = client.fork(sid, instr, adapter_id=1, max_new_tokens=8,
                       speculate=False)["tokens"]
client.close_session(sid)
assert runs[0] == expected, (runs[0], expected)
m = client.metrics()
assert m["fallback_gather_calls"] == 0, m["fallback_gather_calls"]
assert m["queue_depth"] == 0 and m["admission"] == "fairshare"
assert m["speculate"] and m["spec_accepted_tokens"] > 0, \
    (m["speculate"], m["spec_proposed_tokens"], m["spec_accepted_tokens"])
print("http smoke OK: spec-on parity", len(runs[0]), "tokens,",
      "acceptance:", round(m["spec_acceptance_rate"], 3),
      "tenants:", list(m["tenants"]))
PY
echo "== graceful drain (SIGTERM mid-stream, DESIGN.md §17) =="
HTTP_PORT="$HTTP_PORT" HTTP_PID="$HTTP_PID" python - <<'PY'
import os
import signal
import time

import numpy as np

from repro.serving.frontend import ForkClient, HttpError

client = ForkClient(port=int(os.environ["HTTP_PORT"]))
rng = np.random.default_rng(1)
prompt = [int(t) for t in rng.integers(0, 1000, 48)]

# one stream in flight, then SIGTERM: the stream must run to completion
# while new work is refused with 503 + finish_reason="draining".  The
# generation is long so the drain window is comfortably open when the
# refusal probe lands (a short stream drains in milliseconds and the
# server exits before the probe connects).
stream = client.stream_completion(prompt, max_new_tokens=128)
first = next(stream)
os.kill(int(os.environ["HTTP_PID"]), signal.SIGTERM)
time.sleep(0.1)
try:
    client.completion(prompt[:32], max_new_tokens=2)
    raise SystemExit("new request admitted during drain")
except HttpError as exc:
    assert exc.status == 503, exc.status
    assert exc.doc.get("finish_reason") == "draining", exc.doc
    assert float(exc.headers.get("retry-after", 0)) >= 1.0
events = [first] + list(stream)
assert events[-1]["finished"] and len(events[-1]["tokens"]) == 128, events[-1]
print("drain OK: in-flight stream finished, new requests 503")
PY
DRAIN_RC=0
wait $HTTP_PID || DRAIN_RC=$?
test "$DRAIN_RC" -eq 0 || {
  echo "drained server exited rc=$DRAIN_RC"; cat /tmp/forkkv_http.log; exit 1; }
trap - EXIT

echo "== KV persist/restore across restart (DESIGN.md §18) =="
PERSIST_DIR=$(mktemp -d)
start_persist_server() {
  python -m repro.launch.serve --http --port 0 --max-pages 256 \
    --persist-dir "$PERSIST_DIR" --kv-codec zstd \
    > "$1" 2>&1 &
  PERSIST_PID=$!
  trap 'kill $PERSIST_PID 2>/dev/null || true' EXIT
  for _ in $(seq 120); do
    grep -q "on http://" "$1" && break
    sleep 1
  done
  PERSIST_PORT=$(sed -n 's#.*on http://[^:]*:\([0-9]*\).*#\1#p' "$1")
  test -n "$PERSIST_PORT" || { cat "$1"; exit 1; }
}
start_persist_server /tmp/forkkv_persist1.log
HTTP_PORT="$PERSIST_PORT" PHASE=record python - <<'PY'
import json
import os

import numpy as np

from repro.serving.frontend import ForkClient

client = ForkClient(port=int(os.environ["HTTP_PORT"]))
rng = np.random.default_rng(7)
ctx = [int(t) for t in rng.integers(0, 1000, 96)]
sid = client.create_session(ctx, adapter_id=0)
doc = client.fork(sid, ctx[:8], adapter_id=1, max_new_tokens=8)
client.close_session(sid)
assert len(doc["tokens"]) == 8, doc
json.dump({"ctx": ctx, "tokens": doc["tokens"]},
          open("/tmp/forkkv_persist_ref.json", "w"))
print("recorded", len(doc["tokens"]), "tokens before shutdown")
PY
kill -TERM $PERSIST_PID
wait $PERSIST_PID || { cat /tmp/forkkv_persist1.log; exit 1; }
grep -q "persist: wrote" /tmp/forkkv_persist1.log || {
  echo "server did not persist on shutdown"; cat /tmp/forkkv_persist1.log
  exit 1; }
test -s "$PERSIST_DIR/manifest.json" || {
  echo "missing persist manifest"; ls -la "$PERSIST_DIR"; exit 1; }
start_persist_server /tmp/forkkv_persist2.log
grep -q "restore: rehydrated" /tmp/forkkv_persist2.log || {
  echo "restarted server did not restore"; cat /tmp/forkkv_persist2.log
  exit 1; }
HTTP_PORT="$PERSIST_PORT" python - <<'PY'
import json
import os

from repro.serving.frontend import ForkClient

ref = json.load(open("/tmp/forkkv_persist_ref.json"))
client = ForkClient(port=int(os.environ["HTTP_PORT"]))
# the SAME shared context on the restarted server: rehydrated pages must
# serve it as tier hits (no full re-prefill), and the forked greedy
# continuation must be token-identical to the pre-restart run
sid = client.create_session(ref["ctx"], adapter_id=0)
doc = client.fork(sid, ref["ctx"][:8], adapter_id=1, max_new_tokens=8)
client.close_session(sid)
assert doc["tokens"] == ref["tokens"], (doc["tokens"], ref["tokens"])
m = client.metrics()
assert m["restored_pages"] > 0, "nothing was rehydrated"
assert m["tier_hits"] > 0, "restored context was not promoted"
assert m["hit_tokens"] > 0, "session prefill missed the restored prefix"
print(f"persist/restore OK: {m['restored_pages']} pages rehydrated, "
      f"tier_hits={m['tier_hits']}, tokens identical across restart")
PY
kill -TERM $PERSIST_PID
wait $PERSIST_PID || { cat /tmp/forkkv_persist2.log; exit 1; }
trap - EXIT
echo "smoke OK"
