#!/usr/bin/env bash
# CPU smoke gate: everything must at least compile, and the resilience +
# checkpoint recovery paths must pass end-to-end (including the slow
# subprocess drills the tier-1 `-m "not slow"` run excludes).
#
# Usage: bash scripts/ci_smoke.sh
set -euo pipefail
cd "$(dirname "$0")/.."

python -m compileall -q pretraining_llm_tpu scripts

JAX_PLATFORMS=cpu python -m pytest \
    tests/test_resilience.py \
    tests/test_observability.py \
    tests/test_integrity.py \
    tests/test_process_fleet.py \
    tests/test_multihost_fleet.py \
    "tests/test_training.py::test_checkpoint_roundtrip_and_exact_resume" \
    "tests/test_training.py::test_checkpoint_retention" \
    "tests/test_training.py::test_checkpoint_sharded_leaf_reassembly" \
    -q -p no:cacheprovider "$@"

# Observability gate: a tiny synthetic run must emit parseable metrics +
# event streams, and the offline analyzer must accept BOTH with --strict
# (any unparseable line — e.g. a bare NaN token — fails the gate). This is
# what keeps the JSONL schema a checked contract rather than a convention.
OBS_TMP=$(mktemp -d)
trap 'rm -rf "$OBS_TMP"' EXIT
JAX_PLATFORMS=cpu python scripts/train.py --preset tiny --data synthetic \
    --no-resume --steps 8 --obs-dir "$OBS_TMP/obs" \
    --override train.metrics_path="$OBS_TMP/metrics.jsonl" \
    train.checkpoint_dir="$OBS_TMP/ckpt" train.log_interval=2 \
    train.eval_interval=4 train.eval_iters=1 train.checkpoint_interval=4 \
    > "$OBS_TMP/train.out"
test -s "$OBS_TMP/obs/events.jsonl"   # event stream must exist and be non-empty
test -s "$OBS_TMP/obs/spans.trace.json"
python scripts/obs_report.py --strict \
    "$OBS_TMP/metrics.jsonl" "$OBS_TMP/obs/events.jsonl"

# Serving decode gate: 8 requests through the deep-pipelined scheduler
# (depth 2) on a tiny random-init model must finish, emit a token count,
# and report the host-blocked window telemetry — the end-to-end proof
# that dispatch/reap/admission survive outside the pytest fixtures.
JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np
import jax, dataclasses
from pretraining_llm_tpu.config import get_preset
from pretraining_llm_tpu.generation.serving import ServingEngine
from pretraining_llm_tpu.models import transformer

cfg = dataclasses.replace(get_preset("tiny").model, compute_dtype="float32")
params = transformer.init_params(cfg, jax.random.key(0))
eng = ServingEngine(params, cfg, max_batch=4, n_blocks=32, block_size=8,
                    temperature=0.0, steps_per_sched=4, pipeline_depth=2,
                    admit_batch=2)
rng = np.random.default_rng(0)
rids = [eng.submit(rng.integers(0, cfg.vocab_size, size=5 + i).tolist(), 8)
        for i in range(8)]
out = eng.run(pipeline=True)
assert set(out) == set(rids), (sorted(out), rids)
assert all(len(out[r]) == 8 for r in rids), {r: len(out[r]) for r in rids}
st = eng.stats
assert st["windows_reaped"] == st["windows"] > 0, st
assert st["host_blocked_s"] >= 0.0, st
print(f"serving smoke ok: {st['tokens']} tokens, {st['windows']} windows, "
      f"host_blocked_s={st['host_blocked_s']:.4f}")
EOF

# Prefix-cache gate: the SAME shared-prefix workload with the cache off
# and on must produce bit-identical greedy outputs, score real hits, and
# leave the allocator fully accounted for at drain (idle + cold-cached ==
# n_blocks - 1; after flush every block is back on the free list).
JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np
import jax, dataclasses
from pretraining_llm_tpu.config import get_preset
from pretraining_llm_tpu.generation.serving import ServingEngine
from pretraining_llm_tpu.models import transformer

cfg = dataclasses.replace(get_preset("tiny").model, compute_dtype="float32")
params = transformer.init_params(cfg, jax.random.key(0))
rng = np.random.default_rng(3)
head = rng.integers(0, cfg.vocab_size, size=16).tolist()
prompts = [head + rng.integers(0, cfg.vocab_size, size=3 + i).tolist()
           for i in range(6)]

def run(cache):
    eng = ServingEngine(params, cfg, max_batch=2, n_blocks=24, block_size=8,
                        temperature=0.0, steps_per_sched=4, pipeline_depth=2,
                        prefix_cache=cache)
    rids = [eng.submit(p, 8) for p in prompts]
    out = eng.run(pipeline=True)
    return [out[r] for r in rids], eng

off, _ = run(False)
on, eng = run(True)
assert off == on, "prefix cache changed greedy outputs"
st = eng.stats
assert st["prefix_cache_hits"] > 0, st
assert st["prefix_cache_hit_tokens"] > 0, st
assert eng.alloc.available + eng.prefix_cache.evictable == 24 - 1, (
    eng.alloc.available, eng.prefix_cache.evictable)
eng.prefix_cache.flush()
assert eng.alloc.available == 24 - 1, eng.alloc.available
print(f"prefix cache smoke ok: {st['prefix_cache_hits']} hits, "
      f"{st['prefix_cache_hit_tokens']} cached tokens, "
      f"{st['prefill_tokens']} prefill tokens")
EOF

# Chunked-prefill gate: the SAME mixed-length workload with chunking off
# and on (6-token budget, so every longer prompt takes several chunks)
# must produce bit-identical greedy outputs, actually stream chunks, and
# leave the allocator fully accounted for at drain.
JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np
import jax, dataclasses
from pretraining_llm_tpu.config import get_preset
from pretraining_llm_tpu.generation.serving import ServingEngine
from pretraining_llm_tpu.models import transformer

cfg = dataclasses.replace(get_preset("tiny").model, compute_dtype="float32")
params = transformer.init_params(cfg, jax.random.key(0))
rng = np.random.default_rng(7)
prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
           for n in (21, 4, 17, 9, 26, 12)]

def run(chunk):
    eng = ServingEngine(params, cfg, max_batch=3, n_blocks=32, block_size=8,
                        temperature=0.0, steps_per_sched=4, pipeline_depth=2,
                        prefill_chunk_tokens=chunk)
    rids = [eng.submit(p, 8) for p in prompts]
    out = eng.run(pipeline=True)
    return [out[r] for r in rids], eng

off, _ = run(0)
on, eng = run(6)
assert off == on, "chunked prefill changed greedy outputs"
st = eng.stats
assert st["prefill_chunks"] > len(prompts), st  # long prompts took several
assert st["prefill_chunk_tokens"] == sum(len(p) for p in prompts), st
assert eng.alloc.available == 32 - 1, eng.alloc.available
print(f"chunked prefill smoke ok: {st['prefill_chunks']} chunks, "
      f"{st['prefill_chunk_tokens']} chunk tokens, "
      f"interleaved={st['chunk_windows_interleaved']} "
      f"dedicated={st['chunk_windows_dedicated']}")
EOF

# Gateway gate: the ONLINE path end-to-end over real HTTP. A tiny random-
# init model behind EngineLoop + ServingGateway serves 4 concurrent
# requests — one SSE-streaming, one cancelled mid-generation by dropping
# the connection — all must terminate, and /metrics must report the
# request counters (completed + cancelled) in Prometheus text format.
JAX_PLATFORMS=cpu python - <<'EOF'
import dataclasses, json, socket, threading, urllib.request
import jax
from pretraining_llm_tpu.config import get_preset
from pretraining_llm_tpu.frontend.admission import AdmissionController
from pretraining_llm_tpu.frontend.engine_loop import EngineLoop
from pretraining_llm_tpu.frontend.gateway import ServingGateway
from pretraining_llm_tpu.generation.serving import ServingEngine
from pretraining_llm_tpu.models import transformer

cfg = dataclasses.replace(get_preset("tiny").model, compute_dtype="float32")
params = transformer.init_params(cfg, jax.random.key(0))
eng = ServingEngine(params, cfg, max_batch=4, n_blocks=32, block_size=8,
                    temperature=0.0, steps_per_sched=2, pipeline_depth=2)
loop = EngineLoop(eng, admission=AdmissionController(max_queue_depth=8))
gw = ServingGateway(loop, port=0)
loop.start(); gw.start()
base = f"http://127.0.0.1:{gw.port}"

def post(payload):
    req = urllib.request.Request(
        f"{base}/v1/generate", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())

results = {}
def full(name, n):
    results[name] = post({"prompt": [1, 2, 3, int(n)], "max_new_tokens": 8})
def sse(name):
    req = urllib.request.Request(
        f"{base}/v1/generate",
        data=json.dumps({"prompt": [5, 6, 7], "max_new_tokens": 8,
                         "stream": True}).encode(),
        headers={"Content-Type": "application/json"})
    toks, final = [], None
    with urllib.request.urlopen(req, timeout=120) as r:
        for line in r:
            line = line.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            ev = json.loads(line[6:])
            if ev.get("done"): final = ev
            elif "token" in ev: toks.append(ev["token"])
    results[name] = {"tokens": toks, "final": final}
def cancelled(name):
    # Open a streaming request, read one token, drop the socket: the
    # gateway must cancel the request and free its row/pool blocks.
    s = socket.create_connection(("127.0.0.1", gw.port), timeout=120)
    body = json.dumps({"prompt": [9, 9, 9], "max_new_tokens": 48,
                       "stream": True}).encode()
    s.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
              b"Content-Type: application/json\r\n"
              b"Content-Length: " + str(len(body)).encode() + b"\r\n\r\n" + body)
    buf = b""
    while b"data: " not in buf:
        chunk = s.recv(4096)
        assert chunk, buf
        buf += chunk
    s.close()
    results[name] = {"cancel_sent": True}

threads = [threading.Thread(target=full, args=("a", 1)),
           threading.Thread(target=full, args=("b", 2)),
           threading.Thread(target=sse, args=("c",)),
           threading.Thread(target=cancelled, args=("d",))]
for t in threads: t.start()
for t in threads: t.join(timeout=180)
assert not any(t.is_alive() for t in threads), "a gateway request hung"

assert results["a"]["status"] == "done" and results["a"]["n_tokens"] == 8, results["a"]
assert results["b"]["status"] == "done" and results["b"]["n_tokens"] == 8, results["b"]
assert results["c"]["final"]["status"] == "done", results["c"]
assert len(results["c"]["tokens"]) == 8, results["c"]

# The dropped connection must surface as a cancellation (or a completed
# request if the drop raced the final token) — and every row/block must
# be back: allocator idle == n_blocks - 1 (block 0 reserved).
import time
for _ in range(200):
    m = loop.metrics()
    if m["active_requests"] == 0 and eng.alloc.available == 32 - 1:
        break
    time.sleep(0.05)
assert eng.alloc.available == 32 - 1, eng.alloc.available
assert m["completed"] + m["cancelled"] == 4, m

with urllib.request.urlopen(f"{base}/healthz", timeout=30) as r:
    assert json.loads(r.read())["status"] == "ok"
with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
    text = r.read().decode()
assert "pllm_serving_completed" in text, text[:400]
assert "pllm_serving_submitted" in text, text[:400]
assert "pllm_serving_http_requests_total" in text, text[:400]

# Readiness is distinct from liveness: a draining loop keeps /healthz
# green (the process is fine) but must drop out of the balancer.
import urllib.error
with urllib.request.urlopen(f"{base}/readyz", timeout=30) as r:
    assert json.loads(r.read())["status"] == "ready"
loop.begin_drain()
try:
    urllib.request.urlopen(f"{base}/readyz", timeout=30)
    raise AssertionError("/readyz must 503 while draining")
except urllib.error.HTTPError as e:
    assert e.code == 503, e.code
    assert json.loads(e.read())["status"] == "not-ready"
with urllib.request.urlopen(f"{base}/healthz", timeout=30) as r:
    assert json.loads(r.read())["status"] == "ok"

gw.stop(); loop.stop()
print(f"gateway smoke ok: {m}")
EOF

# Tracing gate: the full observability wiring under load. A traced gateway
# serves a seeded loadgen run (every request carrying a W3C traceparent);
# /metrics must be lint-clean Prometheus with histogram counts that agree
# with the terminal-event stream, every response must echo its trace id,
# and the exported Chrome trace must contain a COMPLETE span tree per
# request — enforced by obs_report --strict --slo over the same artifacts
# a production run would ship.
JAX_PLATFORMS=cpu OBS_TMP="$OBS_TMP" python - <<'EOF'
import dataclasses, json, os, urllib.request
import jax
from pretraining_llm_tpu.config import get_preset
from pretraining_llm_tpu.frontend.admission import AdmissionController
from pretraining_llm_tpu.frontend.engine_loop import EngineLoop
from pretraining_llm_tpu.frontend.gateway import ServingGateway
from pretraining_llm_tpu.frontend.loadgen import LoadSpec, run_http
from pretraining_llm_tpu.generation.serving import ServingEngine
from pretraining_llm_tpu.models import transformer
from pretraining_llm_tpu.observability.events import EventBus
from pretraining_llm_tpu.observability.export import lint_exposition
from pretraining_llm_tpu.observability.metrics import MetricsRegistry
from pretraining_llm_tpu.observability.spans import SpanRecorder
from pretraining_llm_tpu.observability.tracing import Tracer

tmp = os.environ["OBS_TMP"]
cfg = dataclasses.replace(get_preset("tiny").model, compute_dtype="float32")
params = transformer.init_params(cfg, jax.random.key(0))
eng = ServingEngine(params, cfg, max_batch=4, n_blocks=32, block_size=8,
                    temperature=0.0, steps_per_sched=2, pipeline_depth=2)
recorder = SpanRecorder()
bus = EventBus(os.path.join(tmp, "serving_events.jsonl"))
registry = MetricsRegistry("pllm_serving_")
loop = EngineLoop(eng, admission=AdmissionController(max_queue_depth=16),
                  bus=bus, tracer=Tracer(recorder, sample=1.0, seed=11),
                  registry=registry)
gw = ServingGateway(loop, port=0, healthz_stale_after_s=30.0)
loop.start(); gw.start()
base = f"http://127.0.0.1:{gw.port}"

spec = LoadSpec(n_requests=8, mode="closed", concurrency=3, seed=5,
                vocab_size=cfg.vocab_size, max_new_min=4, max_new_max=8,
                send_traceparent=True)
report = run_http(base, spec)
by_status = {}
for o in report.outcomes:
    by_status[o.status] = by_status.get(o.status, 0) + 1
    assert o.trace_id, f"request {o.index} lost its trace id: {o}"
assert by_status == {"done": 8}, by_status

with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
    text = r.read().decode()
problems = lint_exposition(text)
assert not problems, problems
count_line = next(
    l for l in text.splitlines()
    if l.startswith("pllm_serving_e2e_seconds_count")
)
assert float(count_line.split()[-1]) == 8.0, count_line

gw.stop(); loop.stop(); bus.close()
terminals = 0
with open(os.path.join(tmp, "serving_events.jsonl")) as f:
    for line in f:
        rec = json.loads(line)
        if rec.get("event") in ("req_done", "req_cancelled",
                                "req_expired", "req_error"):
            terminals += 1
            assert rec.get("trace_id"), rec
assert terminals == 8, terminals
assert recorder.dropped == 0, recorder.dropped
recorder.export(os.path.join(tmp, "serving_trace.json"))
print(f"tracing smoke ok: {by_status}, {terminals} terminal events")
EOF

# The offline analyzer must accept the traced run with --strict --slo:
# every trace tree complete, every SLO-miss attributable, segments
# summing to e2e. A generous e2e SLO keeps this a structural check, not
# a performance bet on the CI machine.
python scripts/obs_report.py --strict --slo --slo_e2e_s 60 \
    "$OBS_TMP/serving_events.jsonl" --trace "$OBS_TMP/serving_trace.json" \
    > "$OBS_TMP/slo_report.out"
grep -q "traces=8 done=8" "$OBS_TMP/slo_report.out" || {
    echo "obs_report --slo missing the expected 8 traces"; exit 1; }

# Capacity gate: the attribution pipeline under REAL pool pressure. A
# deliberately tiny pool (2 rows, 7 allocatable blocks) behind the full
# HTTP stack forces preemptions and cold-cache evictions during a seeded
# traced loadgen run; /debug/engine's pool accounting must agree with the
# allocator, and obs_report --capacity --strict must produce a waterfall
# that sums to wall time within 1% with every decision joined to a known
# trace — the same contract the unit tests check, proved over the wire.
JAX_PLATFORMS=cpu OBS_TMP="$OBS_TMP" python - <<'EOF'
import dataclasses, json, os, urllib.request
import jax
from pretraining_llm_tpu.config import get_preset
from pretraining_llm_tpu.frontend.admission import AdmissionController
from pretraining_llm_tpu.frontend.engine_loop import EngineLoop
from pretraining_llm_tpu.frontend.gateway import ServingGateway
from pretraining_llm_tpu.frontend.loadgen import LoadSpec, run_http
from pretraining_llm_tpu.generation.serving import ServingEngine
from pretraining_llm_tpu.models import transformer
from pretraining_llm_tpu.observability.events import EventBus
from pretraining_llm_tpu.observability.metrics import MetricsRegistry
from pretraining_llm_tpu.observability.spans import SpanRecorder
from pretraining_llm_tpu.observability.tracing import Tracer

tmp = os.environ["OBS_TMP"]
cfg = dataclasses.replace(get_preset("tiny").model, compute_dtype="float32")
params = transformer.init_params(cfg, jax.random.key(0))
# 2 rows over 7 allocatable blocks of 8 tokens: two 10-12 token prompts
# decoding 20-24 tokens each cannot both fit, so growth MUST preempt and
# the prefix cache MUST shed cold blocks.
eng = ServingEngine(params, cfg, max_batch=2, n_blocks=8, block_size=8,
                    temperature=0.0, steps_per_sched=4, pipeline_depth=2,
                    prefix_cache=True)
bus = EventBus(os.path.join(tmp, "capacity_events.jsonl"))
registry = MetricsRegistry("pllm_serving_")
loop = EngineLoop(eng, admission=AdmissionController(max_queue_depth=8),
                  bus=bus, tracer=Tracer(SpanRecorder(), sample=1.0, seed=3),
                  registry=registry)
gw = ServingGateway(loop, port=0)
loop.start(); gw.start()
base = f"http://127.0.0.1:{gw.port}"

spec = LoadSpec(n_requests=6, mode="closed", concurrency=4, seed=11,
                vocab_size=cfg.vocab_size, prompt_len_min=10,
                prompt_len_max=12, max_new_min=20, max_new_max=24,
                send_traceparent=True)
# /debug/requests only lists LIVE requests, so poll it while the load
# runs and keep the richest snapshot we see.
import threading, time
live_snap, stop_poll = [], threading.Event()
def poll():
    while not stop_poll.is_set():
        with urllib.request.urlopen(f"{base}/debug/requests", timeout=30) as r:
            snap = json.loads(r.read())["requests"]
        if len(snap) > len(live_snap):
            live_snap[:] = snap
        time.sleep(0.02)
poller = threading.Thread(target=poll); poller.start()
report = run_http(base, spec)
stop_poll.set(); poller.join(timeout=30)
assert all(o.status == "done" for o in report.outcomes), report.outcomes
assert live_snap and all(r["trace_id"] for r in live_snap), live_snap
assert any(r["phase"] == "decode" and r["row"] is not None
           for r in live_snap), live_snap

with urllib.request.urlopen(f"{base}/debug/engine", timeout=30) as r:
    dbg = json.loads(r.read())
pool = dbg["pool"]
assert pool["total"] == 8 - 1, pool
assert pool["free"] + pool["cold"] + pool["live"] == pool["total"], pool
assert pool["free"] == eng.alloc.available, (pool, eng.alloc.available)
assert pool["cold"] == eng.prefix_cache.evictable, pool
assert dbg["stats"]["preemptions"] >= 1, dbg["stats"]
assert dbg["decisions"]["counts"].get("preempt", 0) >= 1, dbg["decisions"]
assert dbg["decisions"]["counts"].get("evict_cold", 0) >= 1, dbg["decisions"]
assert dbg["windows_sampled"] > 0, dbg

gw.stop(); loop.stop(); bus.close()
print(f"capacity smoke ok: {dbg['stats']['preemptions']} preemptions, "
      f"{dbg['decisions']['counts']}")
EOF

# The analyzer must accept the pressured run with --capacity --strict:
# waterfall segments summing to wall within 1%, every decision joined to
# a known trace, and a named binding constraint.
python scripts/obs_report.py --capacity --strict \
    "$OBS_TMP/capacity_events.jsonl" > "$OBS_TMP/capacity_report.out"
grep -q "binding constraint:" "$OBS_TMP/capacity_report.out" || {
    echo "obs_report --capacity missing the binding constraint"; exit 1; }

# Fleet gate: a 2-replica fleet behind real HTTP with an injected
# replica_crash mid-burst. Every accepted request must reach a terminal
# (zero lost), at least one must have been redriven to the survivor, the
# crashed replica must relaunch, and the merged /metrics exposition must
# stay lint-clean with per-replica labels. The event stream then has to
# survive the offline fleet auditor with --strict (request conservation,
# redrive attribution, recovery timing).
JAX_PLATFORMS=cpu OBS_TMP="$OBS_TMP" python - <<'EOF'
import dataclasses, json, os, time, urllib.request
import jax
from pretraining_llm_tpu.config import get_preset
from pretraining_llm_tpu.frontend.admission import AdmissionController
from pretraining_llm_tpu.frontend.gateway import ServingGateway
from pretraining_llm_tpu.frontend.loadgen import LoadSpec, run_http
from pretraining_llm_tpu.frontend.replica import Replica
from pretraining_llm_tpu.frontend.router import Router
from pretraining_llm_tpu.generation.serving import ServingEngine
from pretraining_llm_tpu.models import transformer
from pretraining_llm_tpu.observability.events import EventBus
from pretraining_llm_tpu.observability.export import lint_exposition
from pretraining_llm_tpu.observability.metrics import MetricsRegistry
from pretraining_llm_tpu.resilience.faults import ServingFaultInjector

tmp = os.environ["OBS_TMP"]
cfg = dataclasses.replace(get_preset("tiny").model, compute_dtype="float32")
params = transformer.init_params(cfg, jax.random.key(0))

def make_engine():
    return ServingEngine(params, cfg, max_batch=2, n_blocks=24, block_size=8,
                         temperature=0.0, steps_per_sched=4, pipeline_depth=2)

bus = EventBus(os.path.join(tmp, "fleet_events.jsonl"))
faults = ServingFaultInjector("replica_crash@req2:r0", bus=bus)
registry = MetricsRegistry("pllm_serving_")
replicas = [
    Replica(i, make_engine, bus=bus, fault_injector=faults,
            admission_factory=lambda reg: AdmissionController(
                max_queue_depth=8, registry=reg))
    for i in range(2)
]
router = Router(replicas, bus=bus, registry=registry,
                admission=AdmissionController(max_queue_depth=16),
                eject_backoff_s=0.2).start()
gw = ServingGateway(router, port=0)
gw.start()
base = f"http://127.0.0.1:{gw.port}"

spec = LoadSpec(n_requests=12, mode="closed", concurrency=4, seed=9,
                vocab_size=cfg.vocab_size, max_new_min=6, max_new_max=10)
report = run_http(base, spec)

lost = spec.n_requests - len(report.outcomes)
assert lost == 0, f"{lost} requests lost"
statuses = {}
for o in report.outcomes:
    statuses[o.status] = statuses.get(o.status, 0) + 1
assert statuses == {"done": 12}, statuses
summary = report.summary()
assert summary["redrives_total"] >= 1, summary
assert router.counters["ejects"] >= 1, router.counters

# The crashed replica must come back (backoff relaunch) before we stop.
deadline = time.monotonic() + 10.0
while time.monotonic() < deadline:
    if all(rep.accepting for rep in router.replicas):
        break
    time.sleep(0.05)
assert router.replicas[0].generation >= 2, router.replicas[0].debug_snapshot()

with urllib.request.urlopen(f"{base}/readyz", timeout=30) as r:
    assert json.loads(r.read())["status"] == "ready"
with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
    text = r.read().decode()
problems = lint_exposition(text)
assert not problems, problems
assert "pllm_serving_redrives_total" in text, text[:400]
assert 'replica="0"' in text and 'replica="1"' in text, text[:400]

gw.stop(); router.stop(); bus.close()
print(f"fleet smoke ok: {statuses}, "
      f"redrives={router.counters['redrives']}, "
      f"ejects={router.counters['ejects']}")
EOF

# The fleet auditor must accept the drill with --strict: conservation
# (every fleet submit reaches exactly one terminal), redrives joined to
# known requests, and a measured recovery for the ejected replica.
python scripts/obs_report.py --fleet --strict \
    "$OBS_TMP/fleet_events.jsonl" > "$OBS_TMP/fleet_report.out"
grep -q "lost=0" "$OBS_TMP/fleet_report.out" || {
    echo "obs_report --fleet did not report lost=0"; exit 1; }
grep -q "redrive cost" "$OBS_TMP/fleet_report.out" || {
    echo "obs_report --fleet missing the redrive cost section"; exit 1; }

# Process-fleet gate: the same drill across a REAL process boundary. Two
# out-of-process workers (each its own engine in its own interpreter)
# behind the router and real HTTP; one worker is SIGKILLed right after
# accepting its 3rd request. Zero lost, at least one redrive onto the
# survivor, the dead worker relaunched as a fresh process, and — after
# shutdown — no orphaned worker processes left on the host.
JAX_PLATFORMS=cpu OBS_TMP="$OBS_TMP" python - <<'EOF'
import json, os, time, urllib.request
from pretraining_llm_tpu.frontend.admission import AdmissionController
from pretraining_llm_tpu.frontend.gateway import ServingGateway
from pretraining_llm_tpu.frontend.loadgen import LoadSpec, run_http
from pretraining_llm_tpu.frontend.remote_replica import RemoteReplica
from pretraining_llm_tpu.frontend.router import Router
from pretraining_llm_tpu.observability.events import EventBus
from pretraining_llm_tpu.observability.export import lint_exposition
from pretraining_llm_tpu.observability.metrics import MetricsRegistry
from pretraining_llm_tpu.resilience.faults import ServingFaultInjector

tmp = os.environ["OBS_TMP"]
bus = EventBus(os.path.join(tmp, "proc_fleet_events.jsonl"))
faults = ServingFaultInjector("worker_kill@req3:r0", bus=bus)
registry = MetricsRegistry("pllm_serving_")
spec = {
    "preset": "tiny",
    "init_seed": 0,
    "model_overrides": {"compute_dtype": "float32"},
    "engine": {"max_batch": 2, "n_blocks": 24, "block_size": 8,
               "temperature": 0.0, "steps_per_sched": 4,
               "pipeline_depth": 2},
    "admission": {"max_queue_depth": 8},
}
replicas = [
    RemoteReplica(i, spec, bus=bus, fault_injector=faults)
    for i in range(2)
]
router = Router(replicas, bus=bus, registry=registry,
                admission=AdmissionController(max_queue_depth=16),
                eject_backoff_s=0.2).start()
gw = ServingGateway(router, port=0)
gw.start()
base = f"http://127.0.0.1:{gw.port}"

load = LoadSpec(n_requests=12, mode="closed", concurrency=4, seed=9,
                vocab_size=replicas[0].engine.cfg.vocab_size,
                max_new_min=6, max_new_max=10)
report = run_http(base, load)

lost = load.n_requests - len(report.outcomes)
assert lost == 0, f"{lost} requests lost"
statuses = {}
for o in report.outcomes:
    statuses[o.status] = statuses.get(o.status, 0) + 1
assert statuses == {"done": 12}, statuses
summary = report.summary()
assert summary["redrives_total"] >= 1, summary
assert router.counters["ejects"] >= 1, router.counters

# The killed worker must come back as a NEW process (backoff relaunch).
deadline = time.monotonic() + 30.0
while time.monotonic() < deadline:
    if all(rep.accepting for rep in router.replicas):
        break
    time.sleep(0.05)
assert router.replicas[0].generation >= 2, router.replicas[0].debug_snapshot()

with urllib.request.urlopen(f"{base}/readyz", timeout=30) as r:
    assert json.loads(r.read())["status"] == "ready"
with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
    text = r.read().decode()
problems = lint_exposition(text)
assert not problems, problems
assert "pllm_serving_worker_spawns_total" in text, text[:400]
assert "pllm_serving_replica_relaunch_total" in text, text[:400]

gw.stop(); router.stop(); bus.close()
print(f"process-fleet smoke ok: {statuses}, "
      f"redrives={router.counters['redrives']}, "
      f"relaunches={router.counters['relaunches']}")
EOF

# No orphaned workers may survive the shutdown (the stdin-watch orphan
# guard plus the router teardown must account for every child).
if pgrep -f "pretraining_llm_tpu.frontend.worker" > /dev/null; then
    echo "orphaned worker processes left after shutdown:"
    pgrep -af "pretraining_llm_tpu.frontend.worker"
    exit 1
fi

# The offline auditor must join the process death to the redrives it
# caused and the relaunch that recovered it.
python scripts/obs_report.py --fleet --strict \
    "$OBS_TMP/proc_fleet_events.jsonl" > "$OBS_TMP/proc_fleet_report.out"
grep -q "lost=0" "$OBS_TMP/proc_fleet_report.out" || {
    echo "obs_report --fleet (process) did not report lost=0"; exit 1; }
grep -q "worker death" "$OBS_TMP/proc_fleet_report.out" || {
    echo "obs_report --fleet missing the worker death join"; exit 1; }

# Multi-host gate: two PRE-SPAWNED workers serving on localhost TCP
# (the router does not own their lifecycle — it attaches by address with
# a shared token, exactly the cross-host deployment shape). Replica 0 is
# blackholed mid-burst: its reads hang and its writes buffer, which is a
# PARTITION, not a connection drop. The router must detect it via lease
# expiry, bump the fence generation, and redrive onto the survivor with
# zero lost requests; on heal, the frames the partitioned worker kept
# streaming (stamped with the old generation) must be counted and
# DROPPED — never forwarded as duplicate tokens. Workers must survive
# router detach (they are not the router's children).
MH_SPEC='{"preset":"tiny","init_seed":0,"model_overrides":{"compute_dtype":"float32"},"engine":{"max_batch":2,"n_blocks":24,"block_size":8,"temperature":0.0,"steps_per_sched":4,"pipeline_depth":2},"admission":{"max_queue_depth":8}}'
JAX_PLATFORMS=cpu python -m pretraining_llm_tpu.frontend.worker \
    --spec-json "$MH_SPEC" --listen 127.0.0.1:0 --token mh-smoke-token \
    > "$OBS_TMP/mh_worker0.out" 2> "$OBS_TMP/mh_worker0.err" &
MH_W0=$!
JAX_PLATFORMS=cpu python -m pretraining_llm_tpu.frontend.worker \
    --spec-json "$MH_SPEC" --listen 127.0.0.1:0 --token mh-smoke-token \
    > "$OBS_TMP/mh_worker1.out" 2> "$OBS_TMP/mh_worker1.err" &
MH_W1=$!

mh_port() {  # wait for the worker's one-line stdout announce, echo port
    local out="$1" port="" i
    for i in $(seq 1 360); do
        if [ -s "$out" ]; then
            port=$(head -n 1 "$out" | python -c 'import json,sys; print(json.loads(sys.stdin.readline())["worker"]["port"])' 2>/dev/null) && \
                [ -n "$port" ] && break
            port=""
        fi
        sleep 0.5
    done
    if [ -z "$port" ]; then
        echo "listen worker never announced a port ($out):" >&2
        cat "${out%.out}.err" >&2
        return 1
    fi
    echo "$port"
}
MH_ADDR0="127.0.0.1:$(mh_port "$OBS_TMP/mh_worker0.out")"
MH_ADDR1="127.0.0.1:$(mh_port "$OBS_TMP/mh_worker1.out")"

JAX_PLATFORMS=cpu OBS_TMP="$OBS_TMP" MH_ADDR0="$MH_ADDR0" \
    MH_ADDR1="$MH_ADDR1" python - <<'EOF'
import json, os, time, urllib.request
from pretraining_llm_tpu.frontend.admission import AdmissionController
from pretraining_llm_tpu.frontend.gateway import ServingGateway
from pretraining_llm_tpu.frontend.loadgen import LoadSpec, run_http
from pretraining_llm_tpu.frontend.remote_replica import RemoteReplica
from pretraining_llm_tpu.frontend.router import Router
from pretraining_llm_tpu.observability.events import EventBus
from pretraining_llm_tpu.observability.export import lint_exposition
from pretraining_llm_tpu.observability.metrics import MetricsRegistry
from pretraining_llm_tpu.resilience.faults import ServingFaultInjector

tmp = os.environ["OBS_TMP"]
bus = EventBus(os.path.join(tmp, "mh_events.jsonl"))
faults = ServingFaultInjector("partition@req2:r0", bus=bus)
registry = MetricsRegistry("pllm_serving_")
spec = {
    "preset": "tiny",
    "init_seed": 0,
    "model_overrides": {"compute_dtype": "float32"},
    "engine": {"max_batch": 2, "n_blocks": 24, "block_size": 8,
               "temperature": 0.0, "steps_per_sched": 4,
               "pipeline_depth": 2},
    "admission": {"max_queue_depth": 8},
}
replicas = []
for i in range(2):
    s = dict(spec)
    s["attach"] = os.environ[f"MH_ADDR{i}"]
    s["token"] = "mh-smoke-token"
    replicas.append(RemoteReplica(i, s, bus=bus, fault_injector=faults,
                                  lease_s=0.8))
# eject_backoff must outlast the drill: a relaunch attempt would tear
# down the blackholed gate and discard the stale frames heal must count.
router = Router(replicas, bus=bus, registry=registry,
                admission=AdmissionController(max_queue_depth=16),
                eject_backoff_s=60.0).start()
gw = ServingGateway(router, port=0)
gw.start()
base = f"http://127.0.0.1:{gw.port}"

load = LoadSpec(n_requests=12, mode="closed", concurrency=4, seed=9,
                vocab_size=replicas[0].engine.cfg.vocab_size,
                max_new_min=6, max_new_max=10)
report = run_http(base, load)

lost = load.n_requests - len(report.outcomes)
assert lost == 0, f"{lost} requests lost"
statuses = {}
for o in report.outcomes:
    statuses[o.status] = statuses.get(o.status, 0) + 1
assert statuses == {"done": 12}, statuses
summary = report.summary()
assert summary["redrives_total"] >= 1, summary
assert router.counters["ejects"] >= 1, router.counters
assert replicas[0].mode == "attach" and replicas[0].proc is None
assert replicas[0]._c_lease.value >= 1, "lease never expired"
assert replicas[0].fence >= 1, "fence generation never bumped"

# Heal the partition: everything the blackholed worker streamed while
# fenced must now arrive, be counted as stale, and be dropped.
replicas[0].heal()
deadline = time.monotonic() + 30.0
while time.monotonic() < deadline:
    if replicas[0]._c_fenced.value >= 1:
        break
    time.sleep(0.05)
assert replicas[0]._c_fenced.value >= 1, "no stale frames were fenced"

with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
    text = r.read().decode()
problems = lint_exposition(text)
assert not problems, problems
assert "pllm_serving_lease_expiries_total" in text, text[:400]
assert "pllm_serving_fenced_frames_total" in text, text[:400]

gw.stop(); router.stop(); bus.close()
print(f"multi-host smoke ok: {statuses}, "
      f"redrives={router.counters['redrives']}, "
      f"lease_expiries={int(replicas[0]._c_lease.value)}, "
      f"fenced={int(replicas[0]._c_fenced.value)}")
EOF

# Detach is not death: the pre-spawned workers must still be alive after
# the router shut down (attach mode never owns the worker lifecycle).
for pid in "$MH_W0" "$MH_W1"; do
    kill -0 "$pid" 2>/dev/null || {
        echo "pre-spawned worker $pid died across router detach"; exit 1; }
done
kill "$MH_W0" "$MH_W1" 2>/dev/null || true
wait "$MH_W0" "$MH_W1" 2>/dev/null || true

# The offline auditor must join the injected partition to its detection
# (lease expiry, not fence drop — the fence notice lands at heal) and to
# the redrives it caused, with zero lost requests.
python scripts/obs_report.py --fleet --strict \
    "$OBS_TMP/mh_events.jsonl" > "$OBS_TMP/mh_report.out"
grep -q "lost=0" "$OBS_TMP/mh_report.out" || {
    echo "obs_report --fleet (multi-host) did not report lost=0"; exit 1; }
grep -q "detected by lease_expiry" "$OBS_TMP/mh_report.out" || {
    echo "obs_report --fleet missing the partition detection join"; exit 1; }

# Integrity gate: a 2-replica fleet with golden probes on and a
# corrupt_kv_page injected on replica 0 mid-burst — the flipped page is
# the probes' own shared prefix block (kv_checksum stays OFF, so the ONLY
# signal is wrong probe output). The sentinel must quarantine the replica,
# zero client requests may be lost and every output must be served by a
# healthy path, the merged /metrics must stay lint-clean with the typed
# integrity counters, and the offline auditor must accept the event
# stream with --integrity --strict (detection attributed, no orphan
# divergence, no unanswered corruption).
JAX_PLATFORMS=cpu OBS_TMP="$OBS_TMP" python - <<'EOF'
import dataclasses, json, os, time, urllib.request
import jax
from pretraining_llm_tpu.config import get_preset
from pretraining_llm_tpu.frontend.admission import AdmissionController
from pretraining_llm_tpu.frontend.gateway import ServingGateway
from pretraining_llm_tpu.frontend.loadgen import LoadSpec, run_http
from pretraining_llm_tpu.frontend.replica import Replica
from pretraining_llm_tpu.frontend.router import Router
from pretraining_llm_tpu.generation.serving import ServingEngine
from pretraining_llm_tpu.models import transformer
from pretraining_llm_tpu.observability.events import EventBus
from pretraining_llm_tpu.observability.export import lint_exposition
from pretraining_llm_tpu.observability.metrics import MetricsRegistry
from pretraining_llm_tpu.resilience.faults import ServingFaultInjector

tmp = os.environ["OBS_TMP"]
cfg = dataclasses.replace(get_preset("tiny").model, compute_dtype="float32")
params = transformer.init_params(cfg, jax.random.key(0))

def make_engine():
    return ServingEngine(params, cfg, max_batch=2, n_blocks=24, block_size=8,
                         temperature=0.0, steps_per_sched=4, pipeline_depth=2,
                         prefix_cache=True)

bus = EventBus(os.path.join(tmp, "integrity_events.jsonl"))
faults = ServingFaultInjector("corrupt_kv_page@req1:r0", bus=bus)
registry = MetricsRegistry("pllm_serving_")
replicas = [
    Replica(i, make_engine, bus=bus, fault_injector=faults)
    for i in range(2)
]
router = Router(replicas, bus=bus, registry=registry,
                admission=AdmissionController(max_queue_depth=16),
                eject_backoff_s=0.2, probe_interval_s=0.05,
                probe_timeout_s=60.0).start()
gw = ServingGateway(router, port=0)
gw.start()
base = f"http://127.0.0.1:{gw.port}"

# Let probe #0 publish its shared prefix page on replica 0 — the fault
# targets the lowest cached block id, i.e. exactly that page.
deadline = time.monotonic() + 30.0
while time.monotonic() < deadline:
    eng = router.replicas[0].engine
    if eng is not None and eng.prefix_cache.cached_block_ids():
        break
    time.sleep(0.05)
assert router.replicas[0].engine.prefix_cache.cached_block_ids(), \
    "probe page never published"

spec = LoadSpec(n_requests=12, mode="closed", concurrency=4, seed=9,
                vocab_size=cfg.vocab_size, max_new_min=6, max_new_max=10)
report = run_http(base, spec)

lost = spec.n_requests - len(report.outcomes)
assert lost == 0, f"{lost} requests lost"
statuses = {}
for o in report.outcomes:
    statuses[o.status] = statuses.get(o.status, 0) + 1
assert statuses == {"done": 12}, statuses

deadline = time.monotonic() + 30.0
while time.monotonic() < deadline:
    if router.counters["quarantines"] >= 1:
        break
    time.sleep(0.05)
assert router.counters["quarantines"] >= 1, router.counters
quar = [d for d in router.decisions.tail()
        if d["decision"] == "quarantine"]
assert quar and quar[0]["replica"] == 0, quar

# The quarantined replica relaunches with fresh weights and a clean pool.
deadline = time.monotonic() + 10.0
while time.monotonic() < deadline:
    if all(rep.accepting for rep in router.replicas):
        break
    time.sleep(0.05)
assert router.replicas[0].generation >= 2, router.replicas[0].debug_snapshot()

with urllib.request.urlopen(f"{base}/debug/engine", timeout=30) as r:
    dbg = json.loads(r.read())
integ = dbg["fleet"]["integrity"]
assert integ["enabled"] and integ["quarantines"] >= 1, integ
with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
    text = r.read().decode()
problems = lint_exposition(text)
assert not problems, problems
assert "pllm_serving_integrity_probes_total" in text, text[:400]
assert "pllm_serving_quarantines_total" in text, text[:400]

gw.stop(); router.stop(); bus.close()
print(f"integrity smoke ok: {statuses}, "
      f"probes={router.counters['probes']}, "
      f"quarantines={router.counters['quarantines']}")
EOF

# The integrity auditor must accept the drill with --strict: the fired
# corruption attributed to a detector, every strict probe divergence
# answered by a quarantine, and no unanswered quarantine.
python scripts/obs_report.py --integrity --strict \
    "$OBS_TMP/integrity_events.jsonl" > "$OBS_TMP/integrity_report.out"
grep -q "detected by" "$OBS_TMP/integrity_report.out" || {
    echo "obs_report --integrity missing the detection attribution"; exit 1; }

# Quantized serving gate: the int8-kv engine behind the full HTTP stack.
# Weights are quantized ONCE up front (per-channel int8 + scale leaves),
# the KV pool holds int8 codes + bf16 scales, and the SAME seeded
# workload (shared prefix + chunked prefill + depth-2 pipelining) run
# twice must produce bit-identical greedy outputs — determinism is the
# contract that makes the integrity sentinel's bit-exact probes possible
# at all. The gate also proves the capacity claim (an equal HBM budget
# holds strictly more int8-kv blocks than bf16) and that /metrics stays
# lint-clean with the quant_dtype const-label and the KV-pool-bytes
# gauges wired.
JAX_PLATFORMS=cpu OBS_TMP="$OBS_TMP" python - <<'EOF'
import dataclasses, json, os, threading, urllib.request
import jax
from pretraining_llm_tpu.config import get_preset
from pretraining_llm_tpu.frontend.admission import AdmissionController
from pretraining_llm_tpu.frontend.engine_loop import EngineLoop
from pretraining_llm_tpu.frontend.gateway import ServingGateway
from pretraining_llm_tpu.generation.serving import ServingEngine
from pretraining_llm_tpu.models import quantize as quantize_mod
from pretraining_llm_tpu.models import transformer
from pretraining_llm_tpu.observability.events import EventBus
from pretraining_llm_tpu.observability.export import lint_exposition
from pretraining_llm_tpu.observability.metrics import MetricsRegistry
from pretraining_llm_tpu.observability.spans import SpanRecorder
from pretraining_llm_tpu.observability.tracing import Tracer

tmp = os.environ["OBS_TMP"]
cfg = dataclasses.replace(get_preset("tiny").model, compute_dtype="float32")
params = transformer.init_params(cfg, jax.random.key(0))
qparams = quantize_mod.quantize_params_for_serving(params, cfg)

# Capacity claim at equal HBM: blocks the int8-kv layout fits into the
# bf16 pool's byte budget must strictly exceed the bf16 block count.
eng_bf = ServingEngine(params, cfg, max_batch=2, n_blocks=24, block_size=8,
                       temperature=0.0)
eng_q = ServingEngine(qparams, cfg, max_batch=2, n_blocks=24, block_size=8,
                      temperature=0.0, quantize="int8-kv")
info_bf, info_q = eng_bf.pool_info(), eng_q.pool_info()
assert info_q["kv_dtype"] == "int8", info_q
assert info_q["kv_scale_dtype"] == "bfloat16", info_q
assert info_q["bytes_per_block"] < info_bf["bytes_per_block"], (info_q, info_bf)
blocks_at_budget = info_bf["pool_bytes"] // info_q["bytes_per_block"]
assert blocks_at_budget > info_bf["n_blocks"], (blocks_at_budget, info_bf)
del eng_bf, eng_q

head = [7, 3, 11, 2, 19, 5, 23, 1, 13, 4, 17, 6]   # shared 12-token prefix
prompts = [head + [31 + 7 * i, 41 + 3 * i, 9 + i][: 2 + i % 3]
           for i in range(8)]

def run_stack(tag):
    eng = ServingEngine(qparams, cfg, max_batch=2, n_blocks=24, block_size=8,
                        temperature=0.0, steps_per_sched=4, pipeline_depth=2,
                        prefix_cache=True, prefill_chunk_tokens=6,
                        quantize="int8-kv")
    bus = EventBus(os.path.join(tmp, f"quant_events_{tag}.jsonl"))
    registry = MetricsRegistry("pllm_serving_",
                               const_labels={"quant_dtype": "int8-kv"})
    loop = EngineLoop(eng, admission=AdmissionController(max_queue_depth=16),
                      bus=bus, tracer=Tracer(SpanRecorder(), sample=1.0,
                                             seed=13),
                      registry=registry)
    gw = ServingGateway(loop, port=0)
    loop.start(); gw.start()
    base = f"http://127.0.0.1:{gw.port}"
    outs = {}
    def post(i, p):
        req = urllib.request.Request(
            f"{base}/v1/generate",
            data=json.dumps({"prompt": p, "max_new_tokens": 8}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            outs[i] = json.loads(r.read())
    threads = [threading.Thread(target=post, args=(i, p))
               for i, p in enumerate(prompts)]
    for t in threads: t.start()
    for t in threads: t.join(timeout=180)
    assert not any(t.is_alive() for t in threads), "a quantized request hung"
    assert all(outs[i]["status"] == "done" and len(outs[i]["tokens"]) == 8
               for i in range(len(prompts))), outs
    with urllib.request.urlopen(f"{base}/debug/engine", timeout=30) as r:
        dbg = json.loads(r.read())
    with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
        text = r.read().decode()
    gw.stop(); loop.stop(); bus.close()
    return [outs[i]["tokens"] for i in range(len(prompts))], dbg, text

out1, dbg, text = run_stack("run1")
out2, _, _ = run_stack("run2")
assert out1 == out2, "int8-kv greedy outputs are not run-to-run identical"

layout = dbg["pool_layout"]
assert layout["quantize"] == "int8-kv", layout
assert layout["kv_dtype"] == "int8", layout
problems = lint_exposition(text)
assert not problems, problems
assert 'quant_dtype="int8-kv"' in text, text[:400]
assert "pllm_serving_kv_pool_bytes" in text, text[:400]
assert "pllm_serving_kv_pool_bytes_per_block" in text, text[:400]
print(f"quantized smoke ok: {len(prompts)} bit-identical requests, "
      f"{layout['bytes_per_block']}B/block int8-kv vs "
      f"{info_bf['bytes_per_block']}B/block bf16 "
      f"({blocks_at_budget} blocks at the bf16 budget)")
EOF

# The capacity auditor must accept the quantized run with --strict: the
# cap_window records now carry the pool's dtype/bytes-per-block identity,
# and the waterfall must still sum and join as before.
python scripts/obs_report.py --capacity --strict \
    "$OBS_TMP/quant_events_run1.jsonl" > "$OBS_TMP/quant_capacity_report.out"
grep -q "binding constraint:" "$OBS_TMP/quant_capacity_report.out" || {
    echo "obs_report --capacity missing the binding constraint (quantized)"; exit 1; }

# Quantized sentinel gate: the corrupt_weights drill on an int8-kv fleet.
# Both replicas serve the SAME pre-quantized params (one quantization up
# front is what keeps the fleet's weight fingerprints and golden probes
# unanimous); the probes are therefore pinned WITHIN the quantized graph
# and compared quantized-vs-quantized, bit-for-bit. Negating a weight
# leaf on replica 0 must trip the sentinel (fingerprint drift / probe
# divergence), quarantine the replica, and redrive its in-flight long
# request to the survivor. Tokens committed inside the detection window
# ran on corrupted weights — that latency is the sentinel's documented
# cost — so bit-identity is asserted where the contract actually holds:
# a post-recovery replay of the whole workload on the healed fleet must
# match a clean single-engine int8-kv reference exactly.
JAX_PLATFORMS=cpu OBS_TMP="$OBS_TMP" python - <<'EOF'
import dataclasses, json, os, threading, time, urllib.request
import jax
from pretraining_llm_tpu.config import get_preset
from pretraining_llm_tpu.frontend.admission import AdmissionController
from pretraining_llm_tpu.frontend.gateway import ServingGateway
from pretraining_llm_tpu.frontend.replica import Replica
from pretraining_llm_tpu.frontend.router import Router
from pretraining_llm_tpu.generation.serving import ServingEngine
from pretraining_llm_tpu.models import quantize as quantize_mod
from pretraining_llm_tpu.models import transformer
from pretraining_llm_tpu.observability.events import EventBus
from pretraining_llm_tpu.observability.export import lint_exposition
from pretraining_llm_tpu.observability.metrics import MetricsRegistry
from pretraining_llm_tpu.resilience.faults import ServingFaultInjector

tmp = os.environ["OBS_TMP"]
cfg = dataclasses.replace(get_preset("tiny").model, compute_dtype="float32")
params = transformer.init_params(cfg, jax.random.key(0))
qparams = quantize_mod.quantize_params_for_serving(params, cfg)

prompts = [[7, 3, 11, 2, 19, 5] + [31 + 7 * i, 9 + i] for i in range(6)]

# Clean reference: every prompt through a single healthy int8-kv engine.
ref_eng = ServingEngine(qparams, cfg, max_batch=2, n_blocks=24, block_size=8,
                        temperature=0.0, steps_per_sched=4,
                        quantize="int8-kv")
rids = [ref_eng.submit(p, 8) for p in prompts]
ref_out = ref_eng.run()
reference = [ref_out[r] for r in rids]
del ref_eng

def make_engine():
    return ServingEngine(qparams, cfg, max_batch=2, n_blocks=24, block_size=8,
                         temperature=0.0, steps_per_sched=4, pipeline_depth=2,
                         prefix_cache=True, quantize="int8-kv")

bus = EventBus(os.path.join(tmp, "quant_integrity_events.jsonl"))
faults = ServingFaultInjector("corrupt_weights@req1:r0", bus=bus)
registry = MetricsRegistry("pllm_serving_",
                           const_labels={"quant_dtype": "int8-kv"})
replicas = [
    Replica(i, make_engine, bus=bus, fault_injector=faults,
            registry_labels={"quant_dtype": "int8-kv"})
    for i in range(2)
]
router = Router(replicas, bus=bus, registry=registry,
                admission=AdmissionController(max_queue_depth=16),
                eject_backoff_s=0.2, probe_interval_s=0.05,
                probe_timeout_s=60.0).start()
gw = ServingGateway(router, port=0)
gw.start()
base = f"http://127.0.0.1:{gw.port}"

def post(p, max_new, out, key):
    req = urllib.request.Request(
        f"{base}/v1/generate",
        data=json.dumps({"prompt": p, "max_new_tokens": max_new}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=180) as r:
        out[key] = json.loads(r.read())

# A long decode pinned in flight while the drill lands: short requests
# walk replica 0's per-replica request count up to the fault trigger,
# and the long one must survive its replica's quarantine via redrive.
drill = {}
long_t = threading.Thread(target=post, args=(prompts[0], 48, drill, "long"))
long_t.start()
for i in range(4):
    post(prompts[1 + i % 4], 4, drill, f"warm{i}")
    if router.counters["quarantines"]:
        break

deadline = time.monotonic() + 30.0
while time.monotonic() < deadline:
    if router.counters["quarantines"] >= 1:
        break
    time.sleep(0.05)
assert router.counters["quarantines"] >= 1, router.counters
long_t.join(timeout=180)
assert not long_t.is_alive(), "the in-flight long request hung"
assert drill["long"]["status"] == "done", drill["long"]
assert len(drill["long"]["tokens"]) == 48, len(drill["long"]["tokens"])
assert drill["long"].get("redrives", 0) >= 1, drill["long"]

# The quarantined replica must relaunch (fresh quantized weights, clean
# pool) and re-pass the quantized-pinned probe/fingerprint checks.
deadline = time.monotonic() + 15.0
while time.monotonic() < deadline:
    if (all(rep.accepting for rep in router.replicas)
            and router.replicas[0].generation >= 2):
        break
    time.sleep(0.05)
assert router.replicas[0].generation >= 2, router.replicas[0].debug_snapshot()

# Post-recovery replay: the healed fleet must be bit-identical to the
# clean int8-kv reference on every prompt.
replay = {}
threads = [threading.Thread(target=post, args=(p, 8, replay, i))
           for i, p in enumerate(prompts)]
for t in threads: t.start()
for t in threads: t.join(timeout=180)
assert not any(t.is_alive() for t in threads), "a replay request hung"
for i, want in enumerate(reference):
    got = replay[i]
    assert got["status"] == "done", got
    assert got["tokens"] == want, (i, got["tokens"], want)

with urllib.request.urlopen(f"{base}/debug/engine", timeout=30) as r:
    dbg = json.loads(r.read())
integ = dbg["fleet"]["integrity"]
assert integ["enabled"] and integ["quarantines"] >= 1, integ
with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
    text = r.read().decode()
problems = lint_exposition(text)
assert not problems, problems
assert 'quant_dtype="int8-kv"' in text, text[:400]
assert "pllm_serving_integrity_probes_total" in text, text[:400]
assert "pllm_serving_quarantines_total" in text, text[:400]

gw.stop(); router.stop(); bus.close()
print(f"quantized sentinel smoke ok: quarantines="
      f"{router.counters['quarantines']}, "
      f"redrives={router.counters['redrives']}, "
      f"{len(prompts)} replayed prompts bit-identical")
EOF

# The integrity auditor must accept the quantized drill with --strict:
# the fired corruption attributed to a detector, every divergence
# answered, no unanswered quarantine.
python scripts/obs_report.py --integrity --strict \
    "$OBS_TMP/quant_integrity_events.jsonl" \
    > "$OBS_TMP/quant_integrity_report.out"
grep -q "detected by" "$OBS_TMP/quant_integrity_report.out" || {
    echo "obs_report --integrity missing the detection attribution (quantized)"; exit 1; }

# Cross-host tracing gate: the distributed-tracing wiring over a REAL
# process boundary. Two pre-spawned TCP workers (proto v2: clock samples
# in hello/heartbeat, batched span-export frames) attach behind a traced
# router; replica 0 is partitioned mid-burst so one request is redriven
# across hosts. The router recorder must end up holding ONE merged
# Chrome trace: worker decode spans clock-aligned into the router
# timeline (offset from the min-RTT estimator, error bound recorded on
# every ingested span) and nested under the owning req.attempt span of
# the router's lineage tree; terminal bodies must carry replica +
# redrives next to trace_id; /metrics must stay lint-clean with the
# span/drop counters and clock gauges; and the offline analyzer must
# accept the artifacts with --fleet-trace --strict.
JAX_PLATFORMS=cpu python -m pretraining_llm_tpu.frontend.worker \
    --spec-json "$MH_SPEC" --listen 127.0.0.1:0 --token trace-smoke-token \
    > "$OBS_TMP/tr_worker0.out" 2> "$OBS_TMP/tr_worker0.err" &
TR_W0=$!
JAX_PLATFORMS=cpu python -m pretraining_llm_tpu.frontend.worker \
    --spec-json "$MH_SPEC" --listen 127.0.0.1:0 --token trace-smoke-token \
    > "$OBS_TMP/tr_worker1.out" 2> "$OBS_TMP/tr_worker1.err" &
TR_W1=$!
TR_ADDR0="127.0.0.1:$(mh_port "$OBS_TMP/tr_worker0.out")"
TR_ADDR1="127.0.0.1:$(mh_port "$OBS_TMP/tr_worker1.out")"

JAX_PLATFORMS=cpu OBS_TMP="$OBS_TMP" TR_ADDR0="$TR_ADDR0" \
    TR_ADDR1="$TR_ADDR1" python - <<'EOF'
import json, os, time, urllib.request
from pretraining_llm_tpu.frontend.admission import AdmissionController
from pretraining_llm_tpu.frontend.gateway import ServingGateway
from pretraining_llm_tpu.frontend.loadgen import LoadSpec, run_http
from pretraining_llm_tpu.frontend.remote_replica import RemoteReplica
from pretraining_llm_tpu.frontend.router import Router
from pretraining_llm_tpu.observability.events import EventBus
from pretraining_llm_tpu.observability.export import lint_exposition
from pretraining_llm_tpu.observability.metrics import MetricsRegistry
from pretraining_llm_tpu.observability.spans import SpanRecorder
from pretraining_llm_tpu.observability.tracing import Tracer
from pretraining_llm_tpu.resilience.faults import ServingFaultInjector

tmp = os.environ["OBS_TMP"]
bus = EventBus(os.path.join(tmp, "fleet_trace_events.jsonl"))
faults = ServingFaultInjector("partition@req2:r0", bus=bus)
registry = MetricsRegistry("pllm_serving_")
# ONE recorder for the whole fleet: the router's own spans and every
# worker's exported spans land in the same buffer, so a single export
# at the end IS the merged cross-host trace.
recorder = SpanRecorder(max_events=50000)
tracer = Tracer(recorder, sample=1.0, seed=17)
spec = {
    "preset": "tiny",
    "init_seed": 0,
    "model_overrides": {"compute_dtype": "float32"},
    "engine": {"max_batch": 2, "n_blocks": 24, "block_size": 8,
               "temperature": 0.0, "steps_per_sched": 4,
               "pipeline_depth": 2},
    "admission": {"max_queue_depth": 8},
}
replicas = []
for i in range(2):
    s = dict(spec)
    s["attach"] = os.environ[f"TR_ADDR{i}"]
    s["token"] = "trace-smoke-token"
    replicas.append(RemoteReplica(i, s, bus=bus, fault_injector=faults,
                                  lease_s=0.8, recorder=recorder))
router = Router(replicas, bus=bus, registry=registry, tracer=tracer,
                admission=AdmissionController(max_queue_depth=16),
                eject_backoff_s=60.0).start()
gw = ServingGateway(router, port=0)
gw.start()
base = f"http://127.0.0.1:{gw.port}"

load = LoadSpec(n_requests=12, mode="closed", concurrency=4, seed=9,
                vocab_size=replicas[0].engine.cfg.vocab_size,
                max_new_min=6, max_new_max=10, send_traceparent=True)
report = run_http(base, load)

lost = load.n_requests - len(report.outcomes)
assert lost == 0, f"{lost} requests lost"
statuses = {}
for o in report.outcomes:
    statuses[o.status] = statuses.get(o.status, 0) + 1
    assert o.trace_id, f"request {o.index} lost its trace id: {o}"
assert statuses == {"done": 12}, statuses
assert report.summary()["redrives_total"] >= 1, report.summary()
assert replicas[0].fence >= 1, "fence generation never bumped"
assert all(rep._peer_proto >= 2 for rep in replicas), \
    [rep._peer_proto for rep in replicas]

# Terminal bodies carry the lineage summary next to the trace id.
req = urllib.request.Request(
    f"{base}/v1/generate",
    data=json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 4}).encode(),
    headers={"Content-Type": "application/json"})
with urllib.request.urlopen(req, timeout=120) as r:
    body = json.loads(r.read())
assert body["status"] == "done" and body.get("trace_id"), body
assert "replica" in body and "redrives" in body, body

# Span export piggybacks on stream ends — wait for the survivor's
# batches to settle before snapshotting the merged trace.
deadline = time.monotonic() + 30.0
last = -1.0
while time.monotonic() < deadline:
    cur = replicas[1]._c_spans.value
    if cur > 0 and cur == last:
        break
    last = cur
    time.sleep(0.5)
assert replicas[1]._c_spans.value > 0, "survivor exported no spans"

with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
    text = r.read().decode()
problems = lint_exposition(text)
assert not problems, problems
assert "pllm_serving_worker_spans_total" in text, text[:400]
assert "pllm_serving_worker_span_drops_total" in text, text[:400]
assert "pllm_serving_clock_offset_seconds" in text, text[:400]
assert "pllm_serving_clock_error_bound_seconds" in text, text[:400]

gw.stop(); router.stop(); bus.close()
recorder.export(os.path.join(tmp, "fleet_trace.json"))

# The merged trace: worker subtrees clock-aligned and nested under the
# router's attempt spans, with at least one redriven lineage tree.
with open(os.path.join(tmp, "fleet_trace.json")) as f:
    events = json.load(f)["traceEvents"]
spans = [e for e in events
         if e.get("ph") == "X" and (e.get("args") or {}).get("trace_id")]
remote = [e for e in spans if e["args"].get("remote")]
assert remote, "no worker spans reached the router recorder"
assert not any(e["args"].get("unaligned") for e in remote), \
    "worker spans ingested without a clock offset estimate"
assert all(e["args"].get("clock_err_s") is not None
           and float(e["args"]["clock_err_s"]) < 0.25 for e in remote), \
    "ingested worker span missing a sane clock error bound"
assert any(e["name"] == "req.window" for e in remote), \
    "no worker decode window in the merged trace"
by_trace = {}
for e in spans:
    by_trace.setdefault(e["args"]["trace_id"], []).append(e)
nested = 0
for tid, grp in by_trace.items():
    attempts = {e["args"].get("span_id") for e in grp
                if e["name"] == "req.attempt" and not e["args"].get("remote")}
    for e in grp:
        if e["args"].get("remote") and e["name"] == "req.request":
            assert e["args"].get("parent_span_id") in attempts, (tid, e)
            nested += 1
assert nested >= 1, "no worker subtree nested under a router attempt"
redriven = [e for e in spans
            if e["name"] == "req.request" and not e["args"].get("remote")
            and int(e["args"].get("redrives") or 0) >= 1]
assert redriven, "no redriven lineage tree in the merged trace"
print(f"cross-host tracing smoke ok: {statuses}, "
      f"{len(remote)} worker spans ({nested} subtrees), "
      f"{len(redriven)} redriven trees, dropped={recorder.dropped}")
EOF

kill "$TR_W0" "$TR_W1" 2>/dev/null || true
wait "$TR_W0" "$TR_W1" 2>/dev/null || true

# The offline analyzer must accept the cross-host artifacts with
# --fleet-trace --strict: every worker span clock-aligned into its
# attempt window, every subtree parented into its lineage tree, and the
# per-request cross-host decomposition summing to e2e.
python scripts/obs_report.py --fleet-trace --strict \
    "$OBS_TMP/fleet_trace_events.jsonl" --trace "$OBS_TMP/fleet_trace.json" \
    > "$OBS_TMP/fleet_trace_report.out"
grep -q "== fleet trace ==" "$OBS_TMP/fleet_trace_report.out" || {
    echo "obs_report --fleet-trace missing the fleet trace section"; exit 1; }
grep -Eq "redriven=[1-9]" "$OBS_TMP/fleet_trace_report.out" || {
    echo "obs_report --fleet-trace saw no redriven lineage tree"; exit 1; }

# Disaggregation gate: a real prefill/decode tier split over TCP. One
# prefill worker + one decode worker (separate processes, roles in the
# spec), hot-prefix traffic through real HTTP: at least one KV page must
# migrate prefill->decode, every request must be served by the decode
# tier with greedy outputs BIT-IDENTICAL to a colocated single engine,
# /metrics must stay lint-clean with the typed migration counters, and
# the offline auditor must join each migration to the prefill it saved.
JAX_PLATFORMS=cpu OBS_TMP="$OBS_TMP" python - <<'EOF'
import dataclasses, json, os, threading, urllib.request
import jax
import numpy as np
from pretraining_llm_tpu.config import get_preset
from pretraining_llm_tpu.frontend.admission import AdmissionController
from pretraining_llm_tpu.frontend.gateway import ServingGateway
from pretraining_llm_tpu.frontend.remote_replica import RemoteReplica
from pretraining_llm_tpu.frontend.router import Router
from pretraining_llm_tpu.generation.serving import ServingEngine
from pretraining_llm_tpu.models import transformer
from pretraining_llm_tpu.observability.events import EventBus
from pretraining_llm_tpu.observability.export import lint_exposition
from pretraining_llm_tpu.observability.metrics import MetricsRegistry

tmp = os.environ["OBS_TMP"]
cfg = dataclasses.replace(get_preset("tiny").model, compute_dtype="float32")
params = transformer.init_params(cfg, jax.random.key(0))
ekw = {"max_batch": 2, "n_blocks": 24, "block_size": 8,
       "temperature": 0.0, "steps_per_sched": 4, "pipeline_depth": 2,
       "prefix_cache": True, "kv_checksum": True}

# Hot-prefix workload: six requests sharing a 12-token prefix — one
# migration of the shared chain warms the decode tier for the rest.
rng = np.random.default_rng(20)
head = rng.integers(0, cfg.vocab_size, size=12).tolist()
prompts = [head + rng.integers(0, cfg.vocab_size, size=3).tolist()
           for _ in range(6)]
n_new = 8

# Colocated reference: one engine, no fleet, no migration.
eng = ServingEngine(params, cfg, **ekw)
rids = {eng.submit(p, n_new): i for i, p in enumerate(prompts)}
ref = {rids[r]: t for r, t in eng.run().items()}

bus = EventBus(os.path.join(tmp, "disagg_events.jsonl"))
registry = MetricsRegistry("pllm_serving_")
def spec(role):
    return {"preset": "tiny", "init_seed": 0,
            "model_overrides": {"compute_dtype": "float32"},
            "engine": dict(ekw), "admission": {"max_queue_depth": 8},
            "role": role}
replicas = [RemoteReplica(0, spec("prefill"), bus=bus),
            RemoteReplica(1, spec("decode"), bus=bus)]
router = Router(replicas, bus=bus, registry=registry,
                admission=AdmissionController(max_queue_depth=16),
                eject_backoff_s=60.0).start()
assert replicas[0].role == "prefill" and replicas[1].role == "decode"
assert all(rep.kv_capable for rep in replicas)
gw = ServingGateway(router, port=0)
gw.start()
base = f"http://127.0.0.1:{gw.port}"

outs = {}
def post(i, p):
    req = urllib.request.Request(
        f"{base}/v1/generate",
        data=json.dumps({"prompt": p, "max_new_tokens": n_new}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=180) as r:
        outs[i] = json.loads(r.read())
threads = [threading.Thread(target=post, args=(i, p))
           for i, p in enumerate(prompts)]
for t in threads: t.start()
for t in threads: t.join(timeout=300)
assert not any(t.is_alive() for t in threads), "a disagg request hung"

for i in range(len(prompts)):
    body = outs[i]
    assert body["status"] == "done", body
    # bit-identity vs colocated: migration must never change a token
    assert body["tokens"] == ref[i], (i, body["tokens"], ref[i])
    # the prefill tier never serves client traffic
    assert body["replica"] == 1, body

assert router.counters["kv_migrations"] >= 1, router.counters
assert router.counters["kv_pages_migrated"] >= 1, router.counters
assert router.counters["kv_migration_rejects"] == 0, router.counters

with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
    text = r.read().decode()
problems = lint_exposition(text)
assert not problems, problems
assert "pllm_serving_kv_pages_migrated_total" in text, text[:400]
assert "pllm_serving_kv_migrated_bytes_total" in text, text[:400]
assert "pllm_serving_kv_migration_rejects_total" in text, text[:400]

gw.stop(); router.stop(); bus.close()
print(f"disaggregation smoke ok: migrations="
      f"{router.counters['kv_migrations']}, pages="
      f"{router.counters['kv_pages_migrated']}, bit-identical over TCP")
EOF

if pgrep -f "pretraining_llm_tpu.frontend.worker" > /dev/null; then
    echo "orphaned worker processes left after disaggregation gate:"
    pgrep -af "pretraining_llm_tpu.frontend.worker"
    exit 1
fi

# The offline auditor must report the migration section: every
# kv_migrate joined to its request, with the prefill tokens it saved.
python scripts/obs_report.py --fleet --strict \
    "$OBS_TMP/disagg_events.jsonl" > "$OBS_TMP/disagg_report.out"
grep -q "lost=0" "$OBS_TMP/disagg_report.out" || {
    echo "obs_report --fleet (disagg) did not report lost=0"; exit 1; }
grep -q "kv migration" "$OBS_TMP/disagg_report.out" || {
    echo "obs_report --fleet missing the kv migration section"; exit 1; }

# Live SLO gate: boot a 2-replica fleet with the SLO engine attached,
# serve a healthy batch over real HTTP, then poll GET /slo — the snapshot
# must be well-formed (distributions, budgets, fleet health) with ZERO
# alerts on a clean run, and obs_report --live must reconcile the live
# sketch quantiles against the exact offline percentiles computed from
# the same run's event stream.
JAX_PLATFORMS=cpu python - "$OBS_TMP" <<'EOF'
import dataclasses, json, subprocess, sys, threading, urllib.request
import jax
import numpy as np
from pretraining_llm_tpu.config import get_preset
from pretraining_llm_tpu.frontend.gateway import ServingGateway
from pretraining_llm_tpu.frontend.replica import Replica
from pretraining_llm_tpu.frontend.router import Router
from pretraining_llm_tpu.generation.serving import ServingEngine
from pretraining_llm_tpu.models import transformer
from pretraining_llm_tpu.observability.capacity import DecisionLog
from pretraining_llm_tpu.observability.events import EventBus
from pretraining_llm_tpu.observability.slo import (
    SLOEngine, default_slo_classes,
)

tmp = sys.argv[1]
cfg = dataclasses.replace(get_preset("tiny").model, compute_dtype="float32")
params = transformer.init_params(cfg, jax.random.key(0))
events_path = f"{tmp}/slo_events.jsonl"
bus = EventBus(events_path)
# Generous objectives (this is a structural gate, not a perf bet) and a
# window wide enough that nothing rotates out before reconciliation.
slo = SLOEngine(
    classes=default_slo_classes(ttft_s=120.0, e2e_s=600.0),
    bus=bus, decisions=DecisionLog(bus=bus), window_s=600.0,
)

def factory():
    return ServingEngine(
        params, cfg, temperature=0.0, max_batch=2, n_blocks=24,
        block_size=8, steps_per_sched=4, pipeline_depth=2,
    )

replicas = [Replica(i, factory, bus=bus) for i in range(2)]
router = Router(replicas, bus=bus, slo=slo, eject_backoff_s=0.1)
router.start()
gw = ServingGateway(router, port=0, slo=slo)
gw.start()
base = f"http://127.0.0.1:{gw.port}"

rng = np.random.default_rng(0)
lengths = (5, 9, 14, 7, 11, 3, 16, 6) * 3  # 24 requests: >= the 20 the
# reconciliation needs before it checks quantiles instead of skipping
prompts = [
    rng.integers(0, cfg.vocab_size, size=int(n)).tolist() for n in lengths
]
outs = {}

def post(i, p):
    req = urllib.request.Request(
        f"{base}/v1/generate",
        data=json.dumps({"prompt": p, "max_new_tokens": 8}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as r:
        outs[i] = json.loads(r.read())

threads = [threading.Thread(target=post, args=(i, p))
           for i, p in enumerate(prompts)]
for t in threads: t.start()
for t in threads: t.join(timeout=600)
assert not any(t.is_alive() for t in threads), "an SLO-gate request hung"
assert all(outs[i]["status"] == "done" for i in range(len(prompts))), outs

with urllib.request.urlopen(base + "/slo", timeout=30) as r:
    snap = json.loads(r.read())
# Well-formed: distributions + budgets + alerts + aggregated fleet health.
assert snap["alerts"]["active"] == [], snap["alerts"]
assert snap["alerts"]["fired_total"] == 0, snap["alerts"]
fleet = snap["latency"]["fleet"]
assert fleet["e2e_s"]["count"] == len(prompts), fleet
assert fleet["ttft_s"]["p99"] > 0
cls = snap["classes"]["interactive"]
assert cls["events"] == len(prompts) and cls["bad"] == 0, cls
fh = snap["fleet_health"]["fleet"]
assert fh["replicas_total"] == 2 and fh["replicas_active"] == 2, fh
assert fh["gauges"]["rows_capacity"] == 4.0, fh["gauges"]

with urllib.request.urlopen(base + "/metricsz", timeout=30) as r:
    mz = json.loads(r.read())
assert "gauges" in mz and "http" in mz, list(mz)

# The analyzer's --live fetch against the SAME gateway + event stream:
# sketch quantiles must land inside the exact offline rank bands.
rc = subprocess.run(
    [sys.executable, "scripts/obs_report.py", "--strict",
     "--live", base, events_path],
).returncode
assert rc == 0, f"obs_report --live --strict failed (rc={rc})"

gw.stop(); router.stop(); bus.close()
print(f"live SLO smoke ok: {len(prompts)} requests, 0 alerts, "
      f"ttft_p99={fleet['ttft_s']['p99']:.3f}s, live reconciled")
EOF

# Fused-sampling gate: fused-vs-unfused greedy decode must be bit-identical
# through the serving engine. A fast version of the tier-1 test, run on every
# smoke.
JAX_PLATFORMS=cpu python - <<'EOF'
import dataclasses

import jax
import numpy as np

from pretraining_llm_tpu.config import get_preset
from pretraining_llm_tpu.generation.serving import ServingEngine
from pretraining_llm_tpu.models import transformer

rng = np.random.default_rng(0)
cfg = dataclasses.replace(get_preset("tiny").model, compute_dtype="float32")
params = transformer.init_params(cfg, jax.random.key(0))
prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
           for n in (5, 9, 14)]
outs = {}
for fused in (True, False):
    eng = ServingEngine(
        params, cfg, temperature=0.0, max_batch=2, n_blocks=24,
        block_size=8, steps_per_sched=3, fused_sampling=fused)
    for p in prompts:
        eng.submit(p, 8)
    outs[fused] = eng.run(pipeline=True)
    host_bytes = eng.stats["logits_bytes_host"]
    assert (host_bytes == 0) == fused, (fused, host_bytes)
assert outs[True] == outs[False], "fused vs unfused greedy drift"
print("decode-fused sampling ok: greedy bit-identical, "
      "0 logits bytes to host when fused")
EOF
