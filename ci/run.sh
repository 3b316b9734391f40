#!/usr/bin/env bash
# CI harness (reference ``ci/`` runtime functions, adapted: no docker — one
# box, two backends).  Stages:
#   unit       - full pytest suite on the virtual 8-device CPU mesh
#   unit_fast  - the suite minus the heavy files (per-commit loop; ~7 min)
#   unit_heavy - only the heavy files (unit == unit_fast + unit_heavy)
#   gate       - multichip SPMD dry-run (dp/tp/sp/pp/ep) via __graft_entry__
#   examples   - fast example-script smoke runs (synthetic data)
#   telemetry  - MXNET_TELEMETRY=1 hybridized train step; assert the
#                chrome trace has >=4 subsystems and >=1 recompile event
#   optimizer  - aggregated multi-tensor update smoke: its tests (they
#                hold the dispatch count of a 200-tensor update and zero
#                steady-state compile misses)
#   serving    - dynamic-batching inference runtime smoke: test_serving.py
#                (zero steady-state compile misses, deadline rejection on
#                a full queue)
#   decode     - generative decode serving smoke: test_decode.py, then a
#                continuous-batching drill — 32 concurrent generate()
#                calls with staggered arrivals and mixed prompt lengths
#                under MXNET_SANITIZE=donation,slots must finish with
#                zero steady-state decode.compile_miss, zero leaked KV
#                slots/pages after drain, >=1 mid-flight join, and zero
#                sanitizer violations; then a speculative-decoding drill
#                (ngram drafter on a repetitive workload) — spec streams
#                bitwise == non-spec, acceptance_rate > 0.3, zero misses
#                / leaks / violations
#   gateway    - HTTP front door smoke: test_gateway.py +
#                test_aot_cache.py, then a 1000-request concurrent
#                /v1/infer drill over real sockets under
#                MXNET_SANITIZE=donation,slots (zero drops, zero
#                non-200), streamed /v1/generate byte-identical to
#                buffered, shed rate > 0 at 2x admission overload with
#                zero 5xx, and a cold-start drill: a restart against a
#                warm on-disk AOT program cache must warm >=5x faster
#                than a no-cache restart and answer bitwise-identically
#   resilience - fault-tolerance smoke: test_resilience.py +
#                test_pod_checkpoint.py (sharded co-writer saves, async,
#                elastic resume), plus a 20-step train loop under
#                MXNET_FAULTS-injected checkpoint-write crashes and one
#                forced NaN step — exact loss parity with a fault-free
#                run, bitwise-identical crash/resume; then a preemption
#                smoke (SIGTERM a 20-step training subprocess mid-run,
#                assert a committed final checkpoint and bitwise resume
#                parity with an uninterrupted run)
#   engine     - lazy-dispatch bulking smoke: test_engine_bulk.py (fused
#                vs eager parity + fallback matrix), then a telemetry
#                parity pass under MXNET_ENGINE_BULK=16 (fused segments
#                recorded, zero steady-state segment compile misses)
#   io         - multi-process input pipeline smoke: test_io_pipeline.py,
#                then a short shm-ring pipeline run (nonzero
#                io.record_batches, zero steady-state augment compile
#                misses) and a clean-teardown sweep of /dev/shm — both on
#                a healthy run and under an injected worker crash
#   analyze    - static-analysis gate + runtime sanitizer smoke: the
#                jax-free tools/analyze.py pass over mxnet_tpu/ (all six
#                checkers incl. the SPMD collectives/barriers divergence
#                family) must report zero findings outside
#                ci/analysis_baseline.txt, then test_analysis.py,
#                test_divergence.py, an MXNET_SANITIZE=donation,slots
#                smoke (planted use-after-donate + post-release shm-slot
#                read must raise with sites named, clean steps zero
#                violations) and a two-simulated-host
#                MXNET_SANITIZE=collectives drill: one clean 2-host SPMD
#                run + sharded commit with zero violations, one planted
#                divergence that must raise CollectiveDivergenceError
#                naming both hosts' next-op fingerprints (bounded by the
#                watchdog, never a hang)
#   trace      - observability smoke: test_trace.py (trace contexts,
#                flight recorder, histograms, HTTP endpoint), then a
#                traced decode drill (one request lane carries
#                submit -> queue wait -> prefill -> rides -> eviction,
#                /metrics and /healthz answer on an ephemeral port) and
#                a two-simulated-host drill: the clean run must merge
#                both hosts' trace streams into ONE valid chrome trace
#                with two process lanes and leave NO flight dump, the
#                planted-divergence run must leave a post-mortem flight
#                dump per host naming each host's last framework events
# Usage: ci/run.sh [stage ...]   (default: unit gate telemetry optimizer
#                                 serving decode gateway resilience
#                                 engine io analyze trace)
set -euo pipefail
cd "$(dirname "$0")/.."

# files dominating wall time (measured with --durations: model-zoo ONNX
# round-trips, SSD, pipeline schedules, multi-process dist, example-driving
# tool tests).  unit_fast excludes exactly these; unit_heavy runs them.
HEAVY_TESTS=(
  tests/test_onnx_model_zoo.py
  tests/test_onnx.py
  tests/test_ssd.py
  tests/test_pipeline.py
  tests/test_tools.py
  tests/test_gluon_model_zoo.py
  tests/test_dist_kvstore.py
  tests/test_moe.py
  tests/test_bert.py
  tests/test_rnn_legacy.py
  tests/test_gluon_rnn.py
  tests/test_parallel.py
  tests/test_spmd_checkpoint.py
  tests/test_quantization_accuracy.py
  tests/test_layout_nhwc.py
  tests/test_chip_consistency.py
)

stage_unit() {
  python -m pytest tests/ -q
}

stage_unit_fast() {
  local ignores=()
  for f in "${HEAVY_TESTS[@]}"; do ignores+=("--ignore=$f"); done
  python -m pytest tests/ -q "${ignores[@]}"
}

stage_unit_heavy() {
  python -m pytest "${HEAVY_TESTS[@]}" -q
}

stage_gate() {
  python - <<'PY'
import __graft_entry__
__graft_entry__.dryrun_multichip(8)
PY
}

stage_examples() {
  python example/gluon/mnist.py --epochs 1
  python example/rnn/word_lm.py --epochs 3 --sentences 200
  python example/sparse/factorization_machine.py --epochs 3 --samples 512
  python example/quantization/quantize_model.py --epochs 4
  python example/profiler/profile_model.py --iters 4
  python example/distributed_training/train_dist.py --iters 5
  python example/rcnn/train_end2end.py --iters 30
  python example/model-parallel/matrix_factorization.py
  python example/gan/dcgan.py --iters 120
  python example/image-classification/fine-tune.py
  python example/multi-task/multi_task.py
  python example/numpy-ops/custom_softmax.py --epochs 5
  python example/amp/finetune_amp.py --epochs 3
  python example/autoencoder/denoising_ae.py --epochs 15
  python example/neural-style/nstyle.py --iters 60
  python example/nce-loss/wordvec.py --epochs 12
  python example/ctc/lstm_ocr_train.py --epochs 10
  python example/fcn-xs/fcn_xs.py --epochs 8
  python example/recommenders/matrix_fact.py --epochs 15
  python example/bi-lstm-sort/bi_lstm_sort.py --epochs 12
  python example/adversary/adversary_generation.py --epochs 10
  python example/cnn_text_classification/text_cnn.py --epochs 8
  python example/svm_mnist/svm_mnist.py --epochs 8
  python example/multivariate_time_series/lstnet_forecast.py --epochs 14
  python example/named_entity_recognition/ner.py --epochs 8
  python example/stochastic-depth/sd_resnet.py --epochs 10
}

stage_telemetry() {
  MXNET_TELEMETRY=1 JAX_PLATFORMS=cpu python - <<'PY'
import json, os, tempfile
import numpy as np
import mxnet_tpu as mx

assert mx.telemetry.is_enabled(), "MXNET_TELEMETRY=1 must enable the bus"

net = mx.gluon.nn.Dense(4)
net.initialize()
net.hybridize()
trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1})
kv = mx.kv.create("local")
kv.init("w", mx.nd.ones((4, 4)))
kv.push("w", mx.nd.ones((4, 4)))
it = mx.io.PrefetchingIter(
    mx.io.NDArrayIter(np.ones((8, 3), "float32"),
                      np.zeros(8, "float32"), batch_size=8))
for batch in it:
    with mx.autograd.record():
        loss = net(batch.data[0]).sum()
    loss.backward()
    trainer.step(8)

path = os.path.join(tempfile.mkdtemp(prefix="telsmoke_"), "trace.json")
mx.telemetry.dump_trace(path)
with open(path) as f:
    doc = json.load(f)                      # valid JSON or this raises
events = doc["traceEvents"]
cats = {e.get("cat") for e in events} - {None}
missing = {"cachedop", "trainer", "kvstore", "io"} - cats
assert not missing, f"trace missing subsystems: {missing} (have {cats})"
recompiles = [e for e in events if e["name"] == "cachedop.recompile"]
assert recompiles, "expected >=1 cachedop.recompile event"
snap = mx.telemetry.snapshot()
assert snap["counters"]["cachedop.recompiles"] >= 1
assert "dispatch.jit_cache_misses" in snap["counters"]
print("telemetry smoke ok:", sorted(cats),
      f"recompiles={len(recompiles)}")
PY
}

stage_optimizer() {
  JAX_PLATFORMS=cpu python -m pytest tests/test_optimizer_aggregate.py -q
}

stage_serving() {
  JAX_PLATFORMS=cpu python -m pytest tests/test_serving.py -q
}

stage_decode() {
  JAX_PLATFORMS=cpu python -m pytest tests/test_decode.py -q
  JAX_PLATFORMS=cpu MXNET_SANITIZE=donation,slots MXNET_TELEMETRY=1 \
      python - <<'PY'
import threading
import time

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.analysis import sanitizer
from mxnet_tpu.serving.decode import DecodeSession, get_decode_model

assert sanitizer.donation and sanitizer.slots, \
    "MXNET_SANITIZE env spec must arm the sanitizer at import"
assert telemetry.is_enabled()

net = get_decode_model("decode_tiny", vocab_size=256, max_length=64)
net.initialize()

# 32 clients sharing 4 system prompts (16 tokens = 2 full pages each) +
# short unique suffixes — the shared-prefix drill: most admissions must
# ride the prefix index, and every stream must be bitwise identical to a
# prefix_sharing=False run of the same requests (fp32 determinism bar)
rng = np.random.RandomState(0)
system = [list(rng.randint(1, 256, 16)) for _ in range(4)]
reqs = [dict(prompt=system[i % 4] + list(rng.randint(1, 256, i % 3)),
             max_new_tokens=6 + (i * 5) % 12,
             temperature=0.8 * (i % 2), seed=i) for i in range(32)]

def drill(prefix_sharing):
    sess = DecodeSession(net, batch_buckets=(1, 2, 4, 8),
                         seq_buckets=(16, 32), page_size=8,
                         queue_depth=256, prefix_sharing=prefix_sharing)
    telemetry.reset()          # miss accounting starts after warmup
    futs = []

    def feed():
        for i, r in enumerate(reqs):
            futs.append(sess.submit(**r))
            time.sleep(0.002 * (i % 3))       # staggered arrivals

    t = threading.Thread(target=feed)
    t.start()
    t.join()
    res = [f.result(timeout=300) for f in futs]
    sess.close(drain=True)
    snap = telemetry.snapshot()["counters"]
    stats = sess.stats()
    assert all(len(r.token_ids) >= 1 for r in res)
    assert not snap.get("decode.compile_miss"), \
        f"steady-state decode recompiles: {snap.get('decode.compile_miss')}"
    assert snap.get("decode.joins", 0) >= 1, \
        "no mid-flight joins — not continuous"
    assert sess.cache.pages_in_use == 0, "leaked KV pages after drain"
    assert sess.cache.slots_in_use == 0, "leaked KV slots after drain"
    sess.cache.drop_prefix_cache()
    assert sess.cache.stats()["prefix_cached_pages"] == 0
    return [r.token_ids for r in res], snap, stats

shared, snap, stats = drill(prefix_sharing=True)
assert stats["prefix_hit_rate"] > 0.5, \
    f"4 hot system prompts must mostly hit: {stats}"
cold, _, cold_stats = drill(prefix_sharing=False)
assert cold_stats["prefix_hits"] == 0
assert shared == cold, "shared-prefix streams diverged from cold prefill"
assert sanitizer.stats()["violations"] == 0, sanitizer.stats()
print("decode smoke ok:", len(shared), "generate() calls,",
      snap["decode.tokens"], "tokens,", snap["decode.steps"], "steps,",
      snap.get("decode.joins"), "joins,",
      f"prefix_hit_rate {stats['prefix_hit_rate']},",
      "bitwise shared==cold, 0 misses, 0 leaks, sanitizer clean")
PY
  # speculative decoding drill: ngram self-drafting on a repetitive
  # workload must (a) hand every request a token stream bitwise equal to
  # the non-speculative run — greedy AND sampled — (b) accept > 30% of
  # proposed draft tokens, (c) take zero steady-state compile misses and
  # leak nothing, all under the donation+slots sanitizers
  JAX_PLATFORMS=cpu MXNET_SANITIZE=donation,slots MXNET_TELEMETRY=1 \
      python - <<'PY'
import numpy as np

from mxnet_tpu import telemetry
from mxnet_tpu.analysis import sanitizer
from mxnet_tpu.serving.decode import (DecodeSession, NgramDrafter,
                                      get_decode_model)

net = get_decode_model("decode_tiny", vocab_size=96, max_length=64,
                       units=32, num_heads=2)
net.initialize()

rng = np.random.RandomState(7)
motifs = [list(rng.randint(1, 96, 4)) for _ in range(4)]
reqs = [dict(prompt=motifs[i % 4] * 3,
             max_new_tokens=10 + i % 6,
             temperature=0.7 * (i % 3 == 0), seed=40 + i)
        for i in range(12)]

def run(drafter):
    sess = DecodeSession(net, batch_buckets=(1, 2, 4), seq_buckets=(16,),
                         page_size=8, drafter=drafter, spec_k=4,
                         start=False)
    telemetry.reset()
    futs = [sess.submit(**r) for r in reqs]
    sess.close(drain=True)
    toks = [f.result().token_ids for f in futs]
    snap = telemetry.snapshot()["counters"]
    assert not snap.get("decode.compile_miss"), \
        f"steady-state recompiles: {snap.get('decode.compile_miss')}"
    assert sess.cache.pages_in_use == 0, "leaked KV pages"
    assert sess.cache.slots_in_use == 0, "leaked KV slots"
    return toks, snap

plain, _ = run(None)
spec, snap = run(NgramDrafter())
assert spec == plain, "speculative streams diverged from non-speculative"
prop = snap.get("decode.spec_proposed", 0)
acc = snap.get("decode.spec_accepted", 0)
assert prop > 0 and acc / prop > 0.3, \
    f"acceptance too low on repetitive workload: {acc}/{prop}"
assert snap.get("decode.spec_steps", 0) >= 1
assert sanitizer.stats()["violations"] == 0, sanitizer.stats()
print("speculative drill ok:", len(spec), "streams bitwise == non-spec,",
      f"acceptance {acc}/{prop} = {acc / prop:.2f},",
      snap.get("decode.spec_bonus", 0), "bonus tokens,",
      "0 misses, 0 leaks, sanitizer clean")
PY
}

stage_gateway() {
  JAX_PLATFORMS=cpu python -m pytest tests/test_gateway.py \
      tests/test_aot_cache.py -q
  # 1k-request concurrent drill at the front door under the sanitizer:
  # every /v1/infer answers 200 over real sockets; streamed /v1/generate
  # carries byte-for-byte the buffered token sequence; at 2x admission
  # overload the box sheds (429 + Retry-After) with ZERO 5xx — pressure
  # is a status code on a healthy gateway, never an error
  JAX_PLATFORMS=cpu MXNET_SANITIZE=donation,slots MXNET_TELEMETRY=1 \
      python - <<'PY'
import http.client
import json
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.analysis import sanitizer
from mxnet_tpu.serving import ModelRegistry, ModelRuntime
from mxnet_tpu.serving.decode import DecodeSession, get_decode_model
from mxnet_tpu.serving.gateway import AdmissionController, Gateway

assert sanitizer.donation and sanitizer.slots
assert telemetry.is_enabled()

reg = ModelRegistry()
net = mx.gluon.nn.HybridSequential()
with net.name_scope():
    net.add(mx.gluon.nn.Dense(32, activation="relu"))
    net.add(mx.gluon.nn.Dense(8))
net.initialize()
rt = ModelRuntime(net, item_shapes=(16,), max_batch=8)
reg.register("m", rt, max_latency_ms=1)

mx.random.seed(0)
dec = get_decode_model("decode_tiny", vocab_size=96, max_length=32,
                       units=32, num_heads=2)
dec.initialize()
sess = DecodeSession(dec, batch_buckets=(1, 2, 4, 8), seq_buckets=(8,),
                     page_size=8, queue_depth=256)
gw = Gateway(registry=reg, capacity=256)
gw.add_decode("tiny", sess)

def post(path, body, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", gw.port,
                                      timeout=timeout)
    try:
        conn.request("POST", path, json.dumps(body),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()

# ---- 1000 concurrent /v1/infer requests, zero drops, zero non-200
N = 1000
x = np.random.RandomState(0).rand(16).astype("float32").tolist()
ref = None
statuses = []
lock = threading.Lock()

def client(i):
    st, raw = post("/v1/infer", {"model": "m", "inputs": x})
    out = json.loads(raw).get("outputs")
    with lock:
        statuses.append((st, out))

with ThreadPoolExecutor(max_workers=16) as pool:
    list(pool.map(client, range(N)))
assert len(statuses) == N, f"dropped responses: {len(statuses)}/{N}"
bad = sorted({st for st, _ in statuses if st != 200})
assert not bad, f"non-200 under healthy load: {bad}"
ref = statuses[0][1]
assert all(out == ref for _, out in statuses), "answers diverged"

# ---- streamed == buffered, byte for byte
for i in range(6):
    req = {"prompt": [2 + i, 5, 9], "max_new_tokens": 8,
           "temperature": 0.8 * (i % 2), "seed": i}
    st, raw = post("/v1/generate", req)
    assert st == 200, raw
    buffered = json.loads(raw)["token_ids"]
    st, raw = post("/v1/generate", dict(req, stream=True))
    assert st == 200
    toks = []
    for chunk in raw.decode().split("\n\n"):
        chunk = chunk.strip()
        if chunk.startswith("data: ") and chunk != "data: [DONE]":
            obj = json.loads(chunk[len("data: "):])
            if "token" in obj:
                toks.append(obj["token"])
    assert toks == buffered, \
        f"SSE stream diverged from buffered: {toks} != {buffered}"

# ---- 2x overload: shed rate > 0, zero 5xx on a healthy box
gw.admission = AdmissionController(capacity=4)
over = []

def overload_client(i):
    st, raw = post("/v1/generate",
                   {"prompt": [7, 7, 7], "max_new_tokens": 16,
                    "temperature": 0.8, "seed": i})
    with lock:
        over.append(st)

with ThreadPoolExecutor(max_workers=8) as pool:
    list(pool.map(overload_client, range(16)))
shed = sum(1 for s in over if s == 429)
assert shed > 0, f"2x overload produced no sheds: {over}"
assert not any(s >= 500 for s in over), f"5xx on a healthy box: {over}"
assert set(over) <= {200, 429}, over

snap = telemetry.snapshot()["counters"]
assert snap.get("gateway.requests", 0) >= N + 12
assert sanitizer.stats()["violations"] == 0, sanitizer.stats()
gw.close()
sess.close(drain=False)
reg.close()
print("gateway drill ok:", N, "infer requests all 200,",
      "6 streams byte-identical to buffered,",
      f"shed {shed}/{len(over)} at 2x overload, 0 5xx, sanitizer clean")
PY
  # cold-start drill: three process restarts through the same on-disk AOT
  # program cache — the cache-warm restart must load every program
  # (0 misses), warm >=5x faster than the no-cache restart, and answer
  # the fixed prompt bitwise-identically
  JAX_PLATFORMS=cpu python - <<'PY'
import json
import os
import subprocess
import sys
import tempfile

worker = os.path.join("tests", "aot_cache_worker.py")
cache = tempfile.mkdtemp(prefix="mxnet-aot-ci-")

def restart(arg):
    out = subprocess.run([sys.executable, worker, arg], check=True,
                         timeout=600, capture_output=True, text=True)
    return json.loads(out.stdout.strip().splitlines()[-1])

no_cache = restart("")
populate = restart(cache)
warm = restart(cache)
assert populate["cache"]["stores"] > 0, populate
assert warm["cache"]["misses"] == 0, warm
assert warm["cache"]["fallbacks"] == 0, warm
assert warm["cache"]["hits"] == populate["cache"]["stores"], warm
assert warm["token_ids"] == populate["token_ids"] == no_cache["token_ids"], \
    "warm-AOT restart must answer bitwise-identically"
speedup = no_cache["warm_s"] / max(warm["warm_s"], 1e-9)
assert speedup >= 5.0, \
    f"warm AOT restart only {speedup:.1f}x faster " \
    f"({no_cache['warm_s']}s -> {warm['warm_s']}s)"
print(f"aot cold-start ok: {no_cache['warm_s']}s no-cache -> "
      f"{warm['warm_s']}s warm ({speedup:.1f}x, "
      f"{warm['cache']['hits']} programs loaded, bitwise restart)")
PY
}

stage_fleet() {
  JAX_PLATFORMS=cpu python -m pytest tests/test_fleet.py -q
  # chaos drill: a proxy gateway over a crash-supervised device-owner.
  # 200 concurrent HTTP requests while the owner is SIGKILLed twice
  # (with a fleet.owner_spawn fault armed so one respawn attempt dies
  # and is retried under backoff).  Contract: every answer is 200/429/
  # 503 (zero 5xx from the crash path), every 200 SSE body terminates
  # with [DONE] (no torn streams), each restart recovers AOT-warm in
  # <=5s, the post-restart owner answers bitwise-identically to the
  # pre-crash cold run, and nothing leaks: KV slots, admission slots,
  # or the unix socket.
  JAX_PLATFORMS=cpu MXNET_TELEMETRY=1 python - <<'PY'
import http.client
import json
import os
import signal
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from mxnet_tpu import telemetry
from mxnet_tpu.resilience import faults
from mxnet_tpu.serving.fleet import Supervisor
from mxnet_tpu.serving.gateway import Gateway

d = tempfile.mkdtemp(prefix="mxnet-fleet-ci-")
sock_path = os.path.join(d, "owner.sock")
sup = Supervisor("tests.fleet_builder:build", sock_path,
                 aot_cache=os.path.join(d, "aot"), heartbeat_s=0.3)
t0 = time.perf_counter()
sup.start()
cold_spawn_s = round(time.perf_counter() - t0, 2)
gw = Gateway(owner=sup, capacity=256)

def post(path, body, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", gw.port,
                                      timeout=timeout)
    try:
        conn.request("POST", path, json.dumps(body),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()

REF = {"model": "decode_tiny", "prompt": [5, 9, 2], "max_new_tokens": 8,
       "temperature": 0.8, "seed": 11, "deadline_ms": 60000}
st, raw = post("/v1/generate", REF)
assert st == 200, (st, raw)
ref_tokens = json.loads(raw)["token_ids"]
assert len(ref_tokens) == 8

N = 200
results = []        # (kind, status, raw)
lock = threading.Lock()

def client(i):
    kind = ("infer", "infer", "generate", "sse")[i % 4]
    if kind == "infer":
        st, raw = post("/v1/infer",
                       {"model": "tiny_dense", "inputs": [0.5] * 8,
                        "deadline_ms": 60000})
    elif kind == "generate":
        st, raw = post("/v1/generate",
                       {"model": "decode_tiny", "prompt": [2 + i % 7, 5],
                        "max_new_tokens": 6, "temperature": 0.8,
                        "seed": i, "deadline_ms": 60000})
    else:
        st, raw = post("/v1/generate",
                       {"model": "decode_tiny", "prompt": [1 + i % 5, 9],
                        "max_new_tokens": 6, "temperature": 0.8,
                        "seed": i, "stream": True, "deadline_ms": 60000})
    with lock:
        results.append((kind, st, raw))

recoveries = []

def killer():
    faults.inject("fleet.owner_spawn", "fail:1")  # one respawn retried
    for _ in range(2):
        while True:
            with lock:
                done = len(results)
            if done >= 20:
                break
            time.sleep(0.05)
        pid = sup.owner_pid
        os.kill(pid, signal.SIGKILL)
        t_kill = time.perf_counter()
        deadline = t_kill + 30.0
        while time.perf_counter() < deadline:
            try:
                c = sup.client()
                c.ping(timeout=2.0)
                c.close()
                break
            except (OSError, TimeoutError):
                time.sleep(0.05)
        recoveries.append(round(time.perf_counter() - t_kill, 2))
        time.sleep(1.5)     # let traffic flow between the two kills

kt = threading.Thread(target=killer)
kt.start()
with ThreadPoolExecutor(max_workers=8) as pool:
    list(pool.map(client, range(N)))
kt.join(timeout=120)
assert not kt.is_alive()

assert len(results) == N, f"dropped responses: {len(results)}/{N}"
bad = sorted({st for _, st, _ in results if st not in (200, 429, 503)})
assert not bad, f"statuses outside 200/429/503 under owner crashes: {bad}"
torn = [raw[-200:] for kind, st, raw in results
        if kind == "sse" and st == 200
        and not raw.rstrip().endswith(b"data: [DONE]")]
assert not torn, f"torn SSE streams: {torn[:3]}"
assert sup.restarts == 2, f"expected 2 restarts, saw {sup.restarts}"
slow = [r for r in recoveries if r > 5.0]
assert not slow, f"AOT-warm recovery must be <=5s, saw {recoveries}"

# post-restart determinism: same request, bitwise the pre-crash answer
st, raw = post("/v1/generate", REF)
assert st == 200, (st, raw)
assert json.loads(raw)["token_ids"] == ref_tokens, \
    "post-restart owner diverged from the pre-crash cold run"

# nothing leaks: KV pages/slots in the owner, admission slots here
cli = sup.client()
stats = cli.call("stats", timeout=30.0)
dec = stats["decode"]["decode_tiny"]
assert dec["pages_in_use"] == 0, dec
assert dec["slots_in_use"] == 0, dec
assert dec["pending"] == 0 and dec["active"] == 0, dec
cli.close()
assert gw.admission.inflight() == 0, gw.admission.snapshot()

counters = telemetry.snapshot()["counters"]
n5xx = sum(1 for _, st, _ in results if st >= 500 and st != 503)
n_unavail = sum(1 for _, st, _ in results if st == 503)
gw.close()
sup.stop()
assert not os.path.exists(sock_path), "owner socket leaked past stop()"
print(f"fleet chaos drill ok: {N} requests through 2 SIGKILLs "
      f"(+1 injected spawn failure), statuses 200/429/503 only "
      f"({n_unavail} x 503), 0 torn SSE, recoveries {recoveries}s "
      f"(cold spawn {cold_spawn_s}s), bitwise post-restart, "
      f"{int(counters.get('gateway.infer_retries', 0))} infer retries, "
      f"0 leaked pages/slots/sockets")
PY
  # SIGTERM drain drill rides in the pytest run above
  # (tests/test_gateway.py::test_sigterm_drains_gracefully)
}

stage_resilience() {
  JAX_PLATFORMS=cpu python -m pytest tests/test_resilience.py -q
  JAX_PLATFORMS=cpu MXNET_FAULTS="checkpoint.write:fail:2" python - <<'PY'
import tempfile
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu.parallel import FunctionalOptimizer, SPMDTrainer, make_mesh
from mxnet_tpu.resilience import ResilientTrainer, faults

assert faults.active, "MXNET_FAULTS env spec must arm the registry at import"

def trainer(seed):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = mx.gluon.nn.HybridSequential(prefix="net_")
    with net.name_scope():
        net.add(mx.gluon.nn.Dense(32, activation="relu", in_units=8),
                mx.gluon.nn.Dense(4, in_units=32))
    net.initialize()
    return SPMDTrainer(net, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
                       FunctionalOptimizer("sgd", 1e-2),
                       make_mesh(n_devices=1, dp=1), nan_guard=True)

rng = np.random.RandomState(0)
batches = [(rng.randn(16, 8).astype("float32"),
            rng.randint(0, 4, 16).astype("float32")) for _ in range(20)]

# fault-free 20-step reference run (it never checkpoints, so the armed
# checkpoint.write spec stays untouched for the faulty run below)
ref_tr = trainer(0)
ref = [float(ref_tr.step(x, y).asnumpy()) for x, y in batches]

# the same 20 steps under a ResilientTrainer checkpointing every 5 steps:
# the env-injected mid-write crashes kill the first two saves, and one
# forced all-NaN step mid-run must be skipped on-device
d = tempfile.mkdtemp(prefix="ci_resilience_")
rt = ResilientTrainer(trainer(0), d, save_every=5)
losses = []
for i, (x, y) in enumerate(batches):
    if i == 8:
        bad = float(rt.step(np.full_like(x, np.nan), y).asnumpy())
        assert not np.isfinite(bad), "forced NaN step must report NaN loss"
    losses.append(float(rt.step(x, y).asnumpy()))
rt.flush()    # judge the final step so its cadence checkpoint commits
assert rt.checkpoint_failures == 2, rt.checkpoint_failures
assert losses == ref, "fault-injected run must match the fault-free run"
latest = rt.manager.latest_step()
assert latest == 20, (latest, rt.manager.complete_steps())

# crash/resume is idempotent: two independent "restarted processes" resume
# at the checkpointed step and replay bitwise-identical steps
probes = []
for seed in (7, 11):
    p = ResilientTrainer(trainer(seed), d, save_every=100)
    assert p.resumed_from == latest and p.step_count == latest, \
        (p.resumed_from, p.step_count)
    probes.append([float(p.step(x, y).asnumpy()) for x, y in batches[:3]])
assert probes[0] == probes[1], probes
print("resilience smoke ok: 20 steps, 2 injected save crashes absorbed,",
      f"1 NaN step skipped, exact loss parity, resume at step {latest}")
PY
  JAX_PLATFORMS=cpu python -m pytest tests/test_pod_checkpoint.py -q
  # preemption smoke: SIGTERM a 20-step training subprocess mid-run; it
  # must exit 0 with a committed final checkpoint, and the resumed run's
  # losses must be bitwise-identical to an uninterrupted 20-step run
  JAX_PLATFORMS=cpu python - <<'PY'
import os, re, signal, subprocess, sys, tempfile
sys.path.insert(0, "tests")
import pod_ckpt_worker as worker

d = tempfile.mkdtemp(prefix="ci_preempt_")
env = dict(os.environ, PYTHONPATH=os.getcwd())
p = subprocess.Popen(
    [sys.executable, "tests/pod_ckpt_worker.py", "--mode", "train-preempt",
     "--dir", d, "--steps", "20", "--save-every", "5",
     "--step-delay", "0.15"],
    stdout=subprocess.PIPE, text=True, bufsize=1, env=env)
lines = []
for line in p.stdout:
    lines.append(line.strip())
    if line.startswith("STEP 7 "):          # mid-run, off the save cadence
        p.send_signal(signal.SIGTERM)
rc = p.wait(timeout=300)
assert rc == 0, (rc, lines[-5:])
pre = next(ln for ln in lines if ln.startswith("PREEMPTED"))
k = int(re.search(r"step=(\d+)", pre).group(1))
assert f"ckpt={k}" in pre, pre
child = [float(ln.split()[2]) for ln in lines if ln.startswith("STEP")]
assert len(child) == k, (len(child), k)

from mxnet_tpu.parallel import SPMDCheckpointManager
assert SPMDCheckpointManager(d).latest_step() == k

from mxnet_tpu.resilience import ResilientTrainer
ref = worker.reference_losses(20)
rt = ResilientTrainer(worker.build_trainer(0), d, save_every=100)
assert rt.resumed_from == k, (rt.resumed_from, k)
resumed = [float(rt.step(x, y).asnumpy())
           for x, y in worker.make_batches(20)[k:]]
assert child + resumed == ref, "preempted+resumed must match uninterrupted"
print(f"preemption smoke ok: SIGTERM at step {k}, clean exit 0,",
      "final checkpoint committed, bitwise-identical resume")
PY
}

stage_engine() {
  JAX_PLATFORMS=cpu python -m pytest tests/test_engine_bulk.py -q
  JAX_PLATFORMS=cpu MXNET_ENGINE_BULK=16 MXNET_TELEMETRY=1 python - <<'PY'
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import engine, telemetry
from mxnet_tpu.engine import recorder

assert engine.bulk_size() == 16, "MXNET_ENGINE_BULK=16 must arm the thread"

# parity: a mixed eager chain under env-armed bulking matches pure numpy
x = mx.nd.array(np.linspace(-2, 2, 64, dtype="float32").reshape(8, 8))
y = ((x * 2.0 + 1.0).relu() - 0.5) / 4.0
z = (y + y.transpose()).sum()
ref = np.linspace(-2, 2, 64, dtype="float32").reshape(8, 8)
ref_y = (np.maximum(ref * 2.0 + 1.0, 0.0) - 0.5) / 4.0
np.testing.assert_allclose(z.asnumpy(), (ref_y + ref_y.T).sum(), rtol=1e-6)

# steady state: repeat the chain; segments replay from cache, zero misses
def chain():
    y = x
    for _ in range(32):
        y = y * 1.0001 + 0.001
    return y
chain().wait_to_read()                       # compile the segment once
c0 = telemetry.snapshot()["counters"]
for _ in range(10):
    chain().wait_to_read()
c1 = telemetry.snapshot()["counters"]
misses = (c1.get("dispatch.segment_compile_miss", 0)
          - c0.get("dispatch.segment_compile_miss", 0))
segs = (c1.get("dispatch.segments_flushed", 0)
        - c0.get("dispatch.segments_flushed", 0))
fused = c1.get("dispatch.ops_fused", 0) - c0.get("dispatch.ops_fused", 0)
assert misses == 0, f"steady-state segment compile misses: {misses}"
assert segs == 40 and fused == 640, (segs, fused)   # 64 ops -> 4 segments
print("engine smoke ok: 64-op chain -> 4 fused segments/step,",
      f"{misses} steady-state compile misses,",
      f"{recorder.cache_info()[0]} cached programs")
PY
}

stage_io() {
  JAX_PLATFORMS=cpu python -m pytest tests/test_io_pipeline.py -q
  JAX_PLATFORMS=cpu MXNET_TELEMETRY=1 python - <<'PY'
import os
import tempfile
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import recordio, telemetry
from mxnet_tpu.resilience import faults


def shm_leaks():
    if not os.path.isdir("/dev/shm"):
        return []
    return [f for f in os.listdir("/dev/shm") if f.startswith("mxio")]


tmp = tempfile.mkdtemp(prefix="ci_io_")
rec_path = os.path.join(tmp, "d.rec")
rng = np.random.RandomState(0)
rec = recordio.MXRecordIO(rec_path, "w")
img = (rng.rand(64, 64, 3) * 255).astype("uint8")
for i in range(96):
    img[i % 64, :, :] = (i * 37) % 255
    rec.write(recordio.pack_img(recordio.IRHeader(0, float(i % 10), i, 0),
                                img, quality=85))
rec.close()

# healthy multi-process run: device-augment prologue, 2 epochs
it = mx.io.ImageRecordIter(path_imgrec=rec_path, data_shape=(3, 48, 48),
                           batch_size=16, rand_mirror=True, shuffle=True,
                           device_augment=True, preprocess_processes=2)
aug = it.augmenter
for _epoch in range(2):
    for b in it:
        aug(b.data[0].asnumpy(), b.augment_flip, b.augment_crop)
    it.reset()
c = telemetry.snapshot()["counters"]
assert c.get("io.record_batches", 0) >= 12, c
assert c.get("io.staging_bytes", 0) > 0, c
assert aug.compile_misses == 1, \
    f"steady-state augment compile misses: {aug.compile_misses - 1}"
it.close()
assert not shm_leaks(), shm_leaks()

# injected worker crash (io.shm_slot hard-kills the worker): the consumer
# must raise within the bounded wait and the shm ring must still unlink
with faults.scope("io.shm_slot:fail:1"):
    it = mx.io.ImageRecordIter(path_imgrec=rec_path,
                               data_shape=(3, 48, 48), batch_size=16,
                               preprocess_processes=2, pipeline_timeout=20)
    try:
        list(it)
        raise AssertionError("injected worker crash must raise")
    except RuntimeError as e:
        assert "died" in str(e), e
    it.close()
assert not shm_leaks(), shm_leaks()
print("io smoke ok:", int(c["io.record_batches"]), "batches,",
      "0 steady-state augment misses, shm clean (healthy + crashed run)")
PY
}

stage_analyze() {
  # static gate first: pure-ast, no jax import (the launcher asserts it)
  python tools/analyze.py --root mxnet_tpu \
    --baseline ci/analysis_baseline.txt -q
  # TestTwoHostDrill is deselected here: the dedicated drill below runs
  # the identical 2-subprocess scenarios with CI-visible assertions, and
  # each drill pair costs two full jax startups
  JAX_PLATFORMS=cpu python -m pytest tests/test_analysis.py \
    tests/test_divergence.py -q -k "not TwoHostDrill"
  JAX_PLATFORMS=cpu MXNET_SANITIZE=donation,slots python - <<'PY'
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu.analysis import sanitizer as san
from mxnet_tpu.optimizer import aggregate

assert san.active and san.donation and san.slots, \
    "MXNET_SANITIZE=donation,slots must arm both modes at import"

opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9)
opt.aggregate_num = 16
ws = [mx.nd.array(np.random.rand(16, 16).astype("float32"))
      for _ in range(8)]
gs = [mx.nd.array(np.random.rand(16, 16).astype("float32"))
      for _ in range(8)]
ss = [opt.create_state_multi_precision(i, w) for i, w in enumerate(ws)]
stale = ws[0].detach()

# clean steps under the sanitizer: zero violations, handles readable
for _ in range(3):
    aggregate.update_multi(opt, list(range(8)), ws, gs, ss)
    _ = [w.asnumpy() for w in ws]
assert san.stats()["violations"] == 0, san.stats()

# planted use-after-donate: must raise and name the aggregated group
try:
    stale.asnumpy()
    raise AssertionError("use-after-donate must raise under the sanitizer")
except san.DonatedBufferError as e:
    assert "optimizer.aggregate group 'sgd'" in str(e), e
assert san.stats()["poisoned"] > 0 and san.stats()["violations"] == 1
print("analyze smoke ok:", san.stats()["poisoned"], "poisoned buffers,",
      "1 planted violation caught, clean steps zero findings")
PY
  # two-simulated-host collective-sanitizer drill (MXNET_CKPT_HOST harness,
  # streams shared via MXNET_SANITIZE_DIR): a clean 2-host SPMD run +
  # sharded checkpoint commit must report zero violations, and a planted
  # divergence (host 1 issues a pipeline schedule where host 0 issues a
  # train step) must raise CollectiveDivergenceError naming BOTH hosts'
  # next-op fingerprints — bounded by the watchdog, never a hang
  JAX_PLATFORMS=cpu python - <<'PY'
import os, subprocess, sys, tempfile

env = dict(os.environ, PYTHONPATH=os.getcwd())
env.pop("MXNET_SANITIZE", None)
env.pop("MXNET_CKPT_HOST", None)

def drill(extra1=()):
    d = tempfile.mkdtemp(prefix="ci_divergence_")
    procs = [subprocess.Popen(
        [sys.executable, "tests/divergence_worker.py", "--dir", d,
         "--host", f"{h}/2", "--steps", "3", "--timeout", "60",
         *(extra1 if h == 1 else ())],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for h in (0, 1)]
    outs = [p.communicate(timeout=300)[0] for p in procs]
    return [p.returncode for p in procs], outs, d

rcs, outs, d = drill()
assert rcs == [0, 0], (rcs, outs)
assert all("violations=0" in o for o in outs), outs
from mxnet_tpu.parallel import SPMDCheckpointManager
assert SPMDCheckpointManager(d).latest_step() == 3, "clean drill must commit"

rcs, outs, d = drill(extra1=("--diverge-at", "2"))
assert rcs == [3, 3], (rcs, outs)       # both raise, neither hangs
for o in outs:
    assert "trainer.step" in o and "pipeline.gpipe" in o, o
    assert "host 0" in o and "host 1" in o, o
assert SPMDCheckpointManager(d).latest_step() is None, \
    "diverged step must never commit"
print("divergence drill ok: clean 2-host commit, planted divergence",
      "raised on both hosts with both fingerprints named")
PY
}

stage_trace() {
  # TestTwoHostDrill is deselected here: the dedicated drill below runs
  # the identical 2-subprocess scenarios with CI-visible assertions
  JAX_PLATFORMS=cpu python -m pytest tests/test_trace.py -q \
    -k "not TwoHostDrill"
  # traced decode drill: one request's lane must carry the full journey,
  # and the live endpoint must answer on an ephemeral port
  JAX_PLATFORMS=cpu MXNET_TELEMETRY=1 python - <<'PY'
import json
import urllib.request

import numpy as np

from mxnet_tpu import telemetry
from mxnet_tpu.serving.decode import (DecodeRuntime, DecodeScheduler,
                                      get_decode_model)
from mxnet_tpu.telemetry import bus, flight, http, trace

assert telemetry.is_enabled() and flight.enabled

net = get_decode_model("decode_tiny", vocab_size=61, max_length=32,
                       units=32, num_heads=2)
net.initialize()
sched = DecodeScheduler(DecodeRuntime(net, batch_buckets=(1, 2),
                                      seq_buckets=(8,), page_size=8))
rng = np.random.RandomState(0)
futs = [sched.submit(list(rng.randint(1, 61, 3 + i)), max_new_tokens=4)
        for i in range(3)]
res = [f.result(timeout=300) for f in futs]
sched.close(drain=True)
assert all(len(r.token_ids) >= 1 for r in res)

roots = [e for e in bus.events() if e[0] == "I" and e[1] == "decode.submit"]
assert len(roots) == 3, len(roots)
lane = (roots[0][6] or {})["trace_id"]
names = [e[1] for e in bus.events() if e[5] == lane]
for hop in ("decode.queue_wait", "decode.ride_prefill", "decode.ride_step",
            "decode.evict"):
    assert hop in names, (hop, names)
hist = telemetry.snapshot()["histograms"]
assert hist["decode.ttft_ms"]["count"] == 3, hist
assert hist["decode.step_ms"]["count"] >= 1, hist
assert any(e[1] == "decode.step" for e in flight.events()), \
    "flight recorder must hold the decode beats by default"

port = http.start_server(0)
with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                            timeout=10) as r:
    body = r.read().decode()
assert r.status == 200 and 'mxnet_decode_ttft_ms_bucket{le="+Inf"} 3' in body
with urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz",
                            timeout=10) as r:
    hz = json.loads(r.read().decode())
assert r.status == 200 and hz["ok"] is True, hz
doc = trace.chrome_trace()
assert doc["traceEvents"], "chrome trace must not be empty"
http.stop_server()
p50 = hist["decode.step_ms"]["p50"]
print(f"trace decode drill ok: 3 request lanes, step p50 {p50}ms,",
      f"/metrics + /healthz on :{port},",
      len(flight.events()), "flight events")
PY
  # two-simulated-host drill (trace streams + flight dumps via env): the
  # clean run merges into ONE valid chrome trace with two host lanes and
  # leaves no flight dump; the planted divergence leaves one per host
  JAX_PLATFORMS=cpu python - <<'PY'
import json, os, subprocess, sys, tempfile

env = dict(os.environ, PYTHONPATH=os.getcwd())
for k in ("MXNET_SANITIZE", "MXNET_CKPT_HOST", "MXNET_TELEMETRY",
          "MXNET_TRACE_DIR", "MXNET_FLIGHT_DIR"):
    env.pop(k, None)

def drill(extra1=()):
    d = tempfile.mkdtemp(prefix="ci_trace_")
    procs = [subprocess.Popen(
        [sys.executable, "tests/trace_host_worker.py", "--dir", d,
         "--host", f"{h}/2", "--steps", "3", "--timeout", "60",
         *(extra1 if h == 1 else ())],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for h in (0, 1)]
    outs = [p.communicate(timeout=300)[0] for p in procs]
    return [p.returncode for p in procs], outs, d

def flight_dumps(d):
    return sorted(f for f in os.listdir(d) if f.startswith("flight-"))

rcs, outs, d = drill()
assert rcs == [0, 0], (rcs, outs)
from mxnet_tpu.telemetry import trace
merged = os.path.join(d, "merged.json")
trace.chrome_trace(path=merged, directory=d)
with open(merged) as f:
    doc = json.load(f)                        # valid JSON or this raises
steps = [e for e in doc["traceEvents"]
         if e.get("ph") == "X" and e["name"] == "trainer.step"]
lanes = {e["pid"] for e in steps}
assert lanes == {0, 1}, (lanes, outs)
assert all("trace_id" in e["args"] for e in steps)
assert flight_dumps(d) == [], "clean run must leave no flight dump"

rcs, outs, d = drill(extra1=("--diverge-at", "2"))
assert rcs == [3, 3], (rcs, outs)
hosts = set()
for name in flight_dumps(d):
    with open(os.path.join(d, name)) as f:
        dump = json.load(f)
    assert dump["reason"] == "CollectiveDivergenceError", dump["reason"]
    hosts.add(dump["host"])
    ev_names = [e["name"] for e in dump["events"]]
    assert "trainer.step" in ev_names and "collective" in ev_names, ev_names
assert hosts == {0, 1}, (hosts, outs)
print("trace drill ok: clean 2-host run merged into one timeline",
      f"({len(steps)} step spans on {len(lanes)} host lanes, 0 dumps),",
      "planted divergence left a flight post-mortem per host")
PY
}

stages=("$@")
[ $# -eq 0 ] && stages=(unit gate telemetry optimizer serving decode
                        gateway fleet resilience engine io analyze trace)
for s in "${stages[@]}"; do
  echo "=== ci stage: $s ==="
  "stage_$s"
done
echo "=== ci: all stages green ==="
