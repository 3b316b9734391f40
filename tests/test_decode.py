"""mxnet_tpu.serving.decode: paged KV cache, 2-D prefill ladder, continuous
batching (ISSUE 11 tentpole + satellites), shared-prefix pages with
copy-on-write + int8 quantized pools (ISSUE 17).

The heart of the file is the no-recompile / bitwise-parity contract test:
a mixed-prompt-length workload with requests joining and finishing across
step boundaries must (a) take zero steady-state ``decode.compile_miss``
and (b) hand every request tokens bitwise-identical to running it solo.
ISSUE 17 adds the sharing analog: a request's tokens are bitwise-identical
whether its prefix was acquired from the shared-prefix index or prefilled
cold — in fp32 AND int8 pools.
"""
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.analysis import StaleKVSlotError, StaleSlotError, sanitizer
from mxnet_tpu.resilience import faults
from mxnet_tpu.resilience.faults import InjectedFault
from mxnet_tpu.serving import RequestRejected
from mxnet_tpu.serving.decode import (DecodeRuntime, DecodeScheduler,
                                      GenerationResult, KVCacheExhausted,
                                      PagedKVCache, get_decode_model,
                                      pages_needed, seq_bucket_ladder)

VOCAB = 61


@pytest.fixture(autouse=True)
def _clean_bus():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


@pytest.fixture(scope="module")
def runtime():
    """One warmed runtime for the whole module (compiles are the cost)."""
    net = get_decode_model("decode_tiny", vocab_size=VOCAB, max_length=32,
                           units=32, num_heads=2)
    net.initialize()
    rt = DecodeRuntime(net, batch_buckets=(1, 2, 4), seq_buckets=(8, 16),
                       page_size=8)
    yield rt


@pytest.fixture(scope="module")
def tight_runtime():
    """Tiny KV pool (3 usable pages) for exhaustion-path tests."""
    net = get_decode_model("decode_tiny", vocab_size=VOCAB, max_length=32,
                           units=32, num_heads=2)
    net.initialize()
    cache = PagedKVCache(net.num_layers, net.num_heads, net.head_dim,
                         page_size=4, num_pages=4, max_pages_per_seq=4,
                         max_slots=2)
    rt = DecodeRuntime(net, cache=cache, batch_buckets=(1, 2),
                       seq_buckets=(8,))
    yield rt


@pytest.fixture
def sched(runtime):
    s = DecodeScheduler(runtime)
    yield s
    s.close(drain=False, timeout=10.0)
    assert runtime.cache.pages_in_use == 0, "leaked KV pages"
    assert runtime.cache.slots_in_use == 0, "leaked KV slots"


def _prompt(i, lo=1, hi=14):
    rng = np.random.RandomState(1000 + i)
    return list(rng.randint(1, VOCAB, lo + (i * 3) % (hi - lo + 1)))


# ------------------------------------------------------------- page math
def test_pages_needed():
    # written positions = prompt + max_new - 1 (last token never re-encoded)
    assert pages_needed(3, 1, 8) == 1
    assert pages_needed(8, 1, 8) == 1
    assert pages_needed(8, 2, 8) == 2
    assert pages_needed(9, 8, 8) == 2
    assert pages_needed(1, 16, 8) == 2


@pytest.mark.parametrize("prompt,max_new", [
    (1, 1), (255, 2), (256, 2), (2048, 64), (5120, 192), (10240, 768),
    (12288 - 767, 768)])
def test_a_row_of_16_tokens_reserves_a_page_every_256(prompt, max_new):
    """A block whose layout states ``row_tokens`` (a chunk summary: one row
    for every 16 tokens) reserves ``ceil(written positions / 256)`` pages of
    16 rows: 48 for a context of 12,288, where a row a token takes 768; the
    cache's context, its page arithmetic and an allocation follow."""
    from mxnet_tpu.serving.decode import EvaLM
    net = EvaLM(window_size=2048, chunk_size=16, max_length=12288)
    cache = PagedKVCache(layout=net.cache_layout(), page_size=16,
                         num_pages=49, max_pages_per_seq=48, max_slots=1)
    assert (cache.page_size, cache.page_tokens) == (16, 256)
    assert cache.context_length == 12288
    written = prompt + max_new - 1
    n = pages_needed(prompt, max_new, cache.page_tokens)
    assert n == -(-written // 256) <= 48
    assert pages_needed(prompt, max_new, cache.page_size) == -(-written // 16)
    slot = cache.alloc(n)
    assert len(slot.pages) == n and len(slot.page_table) == 48 + 1
    cache.free(slot)
    assert cache.stats()["pages_in_use"] == 0


@pytest.mark.parametrize("block", ["CausalLM", "LatentMoELM",
                                   "HybridSSMMoELM", "WindowMoELM",
                                   "LinearMoELM"])
def test_every_other_block_reserves_a_row_a_token_as_it_did(block):
    """The stride is 1 for every block that states none: a page holds
    ``page_size`` tokens, the context is ``pages x page_size`` and a request
    reserves exactly the pages it did."""
    from mxnet_tpu.serving import decode
    from mxnet_tpu.serving.decode import PageFormat
    net = get_decode_model("decode_small", vocab_size=VOCAB) \
        if block == "CausalLM" else getattr(decode, block)()
    layout = net.cache_layout()
    assert "row_tokens" not in layout and "row_shape" not in layout
    assert PageFormat.tokens_a_page(layout, 16) == 16
    cache = PagedKVCache(layout=layout, page_size=8, num_pages=9,
                         max_pages_per_seq=4, max_slots=2)
    assert cache.page_tokens == cache.page_size == 8
    assert cache.context_length == 32
    assert cache.page_bytes == cache.kv_bytes_per_token * 8
    for prompt, max_new in ((3, 1), (8, 2), (9, 8), (1, 16), (20, 13)):
        assert pages_needed(prompt, max_new, cache.page_tokens) == \
            -(-(prompt + max_new - 1) // 8)
    # every pool's row is flat, as the block states its width
    assert [p.shape[3:] for p in cache.pools[:len(cache.pool_layout)]] == \
        [(w,) for _n, w, _d in cache.pool_layout]


def test_seq_bucket_ladder():
    assert seq_bucket_ladder(64) == (8, 16, 32, 64)
    assert seq_bucket_ladder(48) == (8, 16, 32, 48)
    assert seq_bucket_ladder(8) == (8,)
    assert seq_bucket_ladder(4) == (4,)
    with pytest.raises(ValueError):
        seq_bucket_ladder(0)


# ------------------------------------------------------------- KV cache
def test_kv_cache_alloc_free_generations():
    c = PagedKVCache(2, 2, 16, page_size=4, num_pages=9, max_pages_per_seq=4,
                     max_slots=3)
    assert c.usable_pages == 8 and c.context_length == 16
    a = c.alloc(3)
    b = c.alloc(4)
    assert c.pages_in_use == 7 and c.slots_in_use == 2
    assert 0 not in a.pages and 0 not in b.pages          # trash reserved
    assert not (set(a.pages) & set(b.pages))
    assert len(a.page_table) == 4 and a.page_table[3] == 0  # trash-padded
    with pytest.raises(KVCacheExhausted):
        c.alloc(2)                                         # 1 page free
    gen = c.generation(a.slot_id)
    c.free(a)
    assert c.generation(a.slot_id) == gen + 1              # bumped on free
    with pytest.raises(ValueError):
        c.free(a)                                          # double free
    c.free(b)
    assert c.pages_in_use == 0 and c.slots_in_use == 0
    with pytest.raises(ValueError):
        c.alloc(5)                                         # > max_pages_per_seq
    with pytest.raises(ValueError):
        PagedKVCache(2, 2, 16, num_pages=1)                # no room for trash


def test_kv_cache_slot_exhaustion():
    c = PagedKVCache(2, 2, 16, page_size=4, num_pages=9, max_pages_per_seq=2,
                     max_slots=1)
    a = c.alloc(1)
    with pytest.raises(KVCacheExhausted):
        c.alloc(1)                                         # slots, not pages
    c.free(a)
    c.alloc(1)


def test_kv_alloc_fault_injectable():
    c = PagedKVCache(2, 2, 16, page_size=4, num_pages=9, max_pages_per_seq=2)
    with faults.scope("decode.kv_alloc:fail"):
        with pytest.raises(InjectedFault):
            c.alloc(1)
    c.free(c.alloc(1))                                     # healthy after


def test_stale_kv_slot_sanitizer():
    c = PagedKVCache(2, 2, 16, page_size=4, num_pages=9, max_pages_per_seq=2)
    with sanitizer.scope("slots"):
        slot = c.alloc(1)
        c.check_slot(slot)                                 # live: fine
        c.free(slot)
        with pytest.raises(StaleKVSlotError) as ei:
            c.check_slot(slot)
        assert "decode.kv_alloc" in str(ei.value)          # site named
        assert isinstance(ei.value, StaleSlotError)        # slots family
    sanitizer.reset()
    # sanitizer off: the check is a no-op (one attribute read)
    slot = c.alloc(1)
    c.free(slot)
    c.check_slot(slot)


# ----------------------------------------------------------- runtime/ladder
def test_runtime_ladders_and_validation(runtime):
    assert runtime.batch_bucket_for(3) == 4
    assert runtime.seq_bucket_for(9) == 16
    with pytest.raises(ValueError):
        runtime.batch_bucket_for(5)
    with pytest.raises(ValueError):
        runtime.seq_bucket_for(17)
    net = runtime.block
    # cache context must fit the model's position table
    big = PagedKVCache(net.num_layers, net.num_heads, net.head_dim,
                       page_size=8, num_pages=17, max_pages_per_seq=8)
    with pytest.raises(ValueError):
        DecodeRuntime(net, cache=big, warm=False)
    small = PagedKVCache(net.num_layers, net.num_heads, net.head_dim,
                         page_size=8, num_pages=9, max_pages_per_seq=4,
                         max_slots=2)
    with pytest.raises(ValueError):                        # slots < max batch
        DecodeRuntime(net, cache=small, batch_buckets=(1, 4), warm=False)


def test_model_validation():
    with pytest.raises(ValueError):
        get_decode_model("decode_tiny", units=30, num_heads=4)


def test_default_cache_geometry_non_multiple_max_length():
    """Default geometry floors max_length/page_size: the derived context
    never exceeds the model's position table."""
    net = get_decode_model("decode_tiny", vocab_size=VOCAB, max_length=20,
                           units=32, num_heads=2)
    net.initialize()
    rt = DecodeRuntime(net, batch_buckets=(1,), seq_buckets=(8,),
                       page_size=8, warm=False)
    assert rt.cache.context_length == 16                   # 20 // 8 pages
    with pytest.raises(ValueError):
        DecodeRuntime(net, batch_buckets=(1,), page_size=32, warm=False)


# ------------------------------------------------------------- submit plane
def test_submit_validation(sched):
    with pytest.raises(ValueError):
        sched.submit([])                                   # empty
    with pytest.raises(ValueError):
        sched.submit(list(range(1, 18)))                   # > max seq bucket
    with pytest.raises(ValueError):
        sched.submit([VOCAB + 3])                          # id out of range
    with pytest.raises(ValueError):
        sched.submit([1], max_new_tokens=0)
    with pytest.raises(ValueError):
        sched.submit([1] * 16, max_new_tokens=32)          # context overflow


def test_kv_never_fits_shed(tight_runtime):
    s = DecodeScheduler(tight_runtime)
    try:
        # 4 pages needed, 3 usable: could never be admitted
        with pytest.raises(RequestRejected) as ei:
            s.submit([1] * 8, max_new_tokens=8)
        assert ei.value.reason == "kv_exhausted"
    finally:
        s.close(drain=False, timeout=10.0)


def test_kv_exhaustion_waits_then_completes(tight_runtime):
    s = DecodeScheduler(tight_runtime)
    try:
        # each needs 2 of the 3 usable pages: the second waits for the
        # first eviction, then completes — and nothing leaks
        f1 = s.submit(_prompt(1, 4, 4), max_new_tokens=5, seed=1)
        f2 = s.submit(_prompt(2, 4, 4), max_new_tokens=5, seed=2)
        assert len(f1.result(60).token_ids) == 5
        assert len(f2.result(60).token_ids) == 5
    finally:
        s.close(drain=True, timeout=30.0)
    assert tight_runtime.cache.pages_in_use == 0


# ------------------------------------------------------------ generation
def test_generate_deterministic(sched):
    r1 = sched.generate([5, 9, 2], max_new_tokens=6, seed=7, timeout=60)
    r2 = sched.generate([5, 9, 2], max_new_tokens=6, seed=7, timeout=60)
    assert isinstance(r1, GenerationResult)
    assert r1.token_ids == r2.token_ids
    assert r1.finish_reason == "length" and len(r1.token_ids) == 6
    assert r1.prompt_len == 3 and r1.ttft_ms is not None
    t1 = sched.generate([5, 9, 2], max_new_tokens=8, temperature=0.9,
                        seed=11, timeout=60)
    t2 = sched.generate([5, 9, 2], max_new_tokens=8, temperature=0.9,
                        seed=11, timeout=60)
    assert t1.token_ids == t2.token_ids                    # same seed
    streams = [sched.generate([5, 9, 2], max_new_tokens=8, temperature=0.9,
                              seed=s, timeout=60).token_ids
               for s in (21, 22, 23)]
    assert len({tuple(s) for s in streams}) > 1            # seeds matter


def test_eos_stops_early(sched):
    ref = sched.generate([3, 1, 4, 1, 5], max_new_tokens=6, seed=0,
                         timeout=60).token_ids
    eos = ref[-1]
    idx = ref.index(eos)
    out = sched.generate([3, 1, 4, 1, 5], max_new_tokens=6, seed=0,
                         eos_id=eos, timeout=60)
    assert out.finish_reason == "eos"
    assert out.token_ids == ref[:idx + 1]


def test_cancelled_request_evicted(sched):
    # cancel while still queued behind a full batch: slot is never held
    blockers = [sched.submit(_prompt(i, 6, 6), max_new_tokens=16, seed=i)
                for i in range(4)]
    victim = sched.submit([1, 2], max_new_tokens=16)
    victim.cancel()
    [b.result(60) for b in blockers]
    assert victim.cancelled()


# ------------------------------------- THE no-recompile / parity contract
def test_continuous_batching_bitwise_parity_and_zero_misses(runtime):
    reqs = [dict(prompt=_prompt(i), max_new_tokens=3 + i % 6,
                 temperature=0.7 * (i % 3 == 0), seed=100 + i)
            for i in range(12)]
    s = DecodeScheduler(runtime)
    try:
        # solo reference: one request at a time (batch bucket 1)
        solo = [s.generate(timeout=120, **r).token_ids for r in reqs]
        # drop the prefix index the solo pass just populated: the
        # continuous pass must prefill cold so requests genuinely
        # overlap (full-prefix hits admit instantly and the batch can
        # drain between staggered arrivals) — and cold-vs-published is
        # exactly the parity this test exists to prove
        runtime.cache.drop_prefix_cache()
        telemetry.enable()
        telemetry.reset()
        futs = []

        def feed():
            for i, r in enumerate(reqs):
                futs.append(s.submit(**r))
                time.sleep(0.002 * (i % 4))

        t = threading.Thread(target=feed)
        t.start()
        t.join()
        cont = [f.result(120).token_ids for f in futs]
        snap = telemetry.snapshot()["counters"]
        telemetry.disable()
    finally:
        s.close(drain=False, timeout=10.0)
    for i, (a, b) in enumerate(zip(solo, cont)):
        assert a == b, f"request {i} diverged: solo={a} continuous={b}"
    assert not snap.get("decode.compile_miss"), snap
    assert snap.get("decode.joins", 0) >= 1          # genuinely continuous
    assert snap["decode.evictions"] == len(reqs)
    assert runtime.cache.pages_in_use == 0, "leaked KV pages"
    assert runtime.cache.slots_in_use == 0, "leaked KV slots"


def test_sanitizer_clean_continuous_run(runtime):
    s = DecodeScheduler(runtime)
    try:
        with sanitizer.scope("donation,slots"):
            futs = [s.submit(_prompt(i), max_new_tokens=4, seed=i)
                    for i in range(6)]
            [f.result(60) for f in futs]
            assert sanitizer.stats()["violations"] == 0
    finally:
        sanitizer.reset()
        s.close(drain=False, timeout=10.0)


def test_mesh_sharded_kv_cache_parity():
    """NamedSharding over the heads axis: the cache scales with the mesh
    without changing scheduler code, and decode output is unchanged."""
    import jax
    from jax.sharding import Mesh, NamedSharding
    from mxnet_tpu.serving.decode import DecodeSession
    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 devices")
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 4), ("data", "model"))
    net = get_decode_model("decode_tiny", vocab_size=VOCAB, max_length=32,
                           units=32, num_heads=4)
    net.initialize()
    sess = DecodeSession(net, batch_buckets=(1, 2), seq_buckets=(8,),
                         page_size=8, mesh=mesh)
    try:
        assert isinstance(sess.cache.k_pages.sharding, NamedSharding)
        assert "model" in str(sess.cache.k_pages.sharding.spec)
        sharded = sess.generate([5, 9, 2], max_new_tokens=5, seed=7,
                                timeout=120).token_ids
    finally:
        sess.close(drain=False)
    plain = DecodeSession(net, batch_buckets=(1, 2), seq_buckets=(8,),
                          page_size=8)
    try:
        assert plain.generate([5, 9, 2], max_new_tokens=5, seed=7,
                              timeout=120).token_ids == sharded
    finally:
        plain.close(drain=False)


# --------------------------------------------------------- shed/backpressure
def test_deadline_shed_while_waiting(sched):
    # 4 long sequences fill every batch row; a deadlined request behind
    # them expires at the next admission sweep instead of hanging
    blockers = [sched.submit(_prompt(i, 6, 6), max_new_tokens=20, seed=i)
                for i in range(4)]
    while sched.active() < 4 and not all(b.done() for b in blockers):
        time.sleep(0.001)
    late = sched.submit([1, 2, 3], max_new_tokens=4, deadline_ms=2)
    with pytest.raises(RequestRejected) as ei:
        late.result(60)
    assert ei.value.reason == "deadline"
    [b.result(120) for b in blockers]


def test_queue_backpressure_deadline(runtime):
    s = DecodeScheduler(runtime, queue_depth=1, start=False)
    try:
        s.submit([1, 2], max_new_tokens=2)
        with pytest.raises(RequestRejected) as ei:
            s.submit([3, 4], max_new_tokens=2, deadline_ms=30)
        assert ei.value.reason == "deadline"
    finally:
        s.close(drain=True, timeout=30.0)


def test_close_drain_false_rejects(runtime):
    s = DecodeScheduler(runtime, start=False)
    f = s.submit([1, 2, 3], max_new_tokens=4)
    s.close(drain=False)
    with pytest.raises(RequestRejected) as ei:
        f.result(5)
    assert ei.value.reason == "shutdown"
    with pytest.raises(RequestRejected):
        s.submit([1], max_new_tokens=1)
    assert runtime.cache.pages_in_use == 0


def test_close_drain_true_completes(runtime):
    s = DecodeScheduler(runtime, start=False)
    futs = [s.submit(_prompt(i), max_new_tokens=3, seed=i) for i in range(5)]
    s.close(drain=True, timeout=60.0)
    for f in futs:
        assert len(f.result(0).token_ids) == 3
    assert runtime.cache.pages_in_use == 0


# ------------------------------------------------------------ fault drills
def test_step_fault_fails_batch_and_recovers(runtime):
    s = DecodeScheduler(runtime, breaker_threshold=None)
    try:
        with faults.scope("decode.step:fail"):
            f = s.submit([1, 2, 3], max_new_tokens=4, seed=0)
            with pytest.raises(InjectedFault):
                f.result(60)
        assert runtime.cache.pages_in_use == 0             # slot freed
        ok = s.generate([1, 2, 3], max_new_tokens=4, seed=0, timeout=60)
        assert len(ok.token_ids) == 4                      # worker survived
        assert s.steps_failed == 1
    finally:
        s.close(drain=False, timeout=10.0)


def test_kv_alloc_fault_sheds_request_only(runtime):
    s = DecodeScheduler(runtime)
    try:
        with faults.scope("decode.kv_alloc:fail"):
            f = s.submit([1, 2], max_new_tokens=3, seed=0)
            with pytest.raises(InjectedFault):
                f.result(60)
        ok = s.generate([1, 2], max_new_tokens=3, seed=0, timeout=60)
        assert len(ok.token_ids) == 3
    finally:
        s.close(drain=False, timeout=10.0)


def test_circuit_breaker_opens_and_probes(runtime):
    s = DecodeScheduler(runtime, breaker_threshold=1,
                        breaker_cooldown_ms=150.0)
    try:
        with faults.scope("decode.step:fail"):
            f = s.submit([1, 2, 3], max_new_tokens=4)
            with pytest.raises(InjectedFault):
                f.result(60)
        assert not s.healthy
        with pytest.raises(RequestRejected) as ei:
            s.submit([1], max_new_tokens=2)
        assert ei.value.reason == "unhealthy"
        time.sleep(0.2)                                    # cooldown expires
        assert s.healthy
        assert len(s.generate([1, 2, 3], max_new_tokens=3,
                              timeout=60).token_ids) == 3
    finally:
        s.close(drain=False, timeout=10.0)


def test_dead_scheduler_collected_inside_a_registration(runtime, monkeypatch):
    """A dead scheduler is cyclic garbage, and the collector may run its
    ``__del__`` (``close`` -> ``unregister_ready``) on the thread that HOLDS
    the registries' lock, at the weakref ``_register`` allocates under it:
    that registration comes back and does not wait for its own lock."""
    import gc
    import types
    import weakref

    from mxnet_tpu.telemetry import http

    class Probe:
        ready = True

    def ref_with_the_collector_in_it(obj):
        gc.collect()
        return weakref.ref(obj)

    monkeypatch.setattr(
        http, "weakref", types.SimpleNamespace(ref=ref_with_the_collector_in_it))
    probe, gone = Probe(), []
    gc.collect()
    gc.disable()            # the dead scheduler waits for the collect above
    try:
        dead = DecodeScheduler(runtime, start=False)
        dead.close(drain=False, timeout=10.0)
        dead._itself = dead
        weakref.finalize(dead, gone.append, True)
        del dead
        registering = threading.Thread(
            target=http.register_ready, args=("probe:d20", probe), daemon=True)
        registering.start()
        registering.join(20.0)
    finally:
        gc.enable()
    assert not registering.is_alive(), \
        "register_ready waits for a lock its own thread holds"
    assert gone == [True]
    assert http.readiness()[1].get("probe:d20") is True
    http.unregister_ready("probe:d20", probe)


# ------------------------------------------------------------- telemetry
def test_decode_telemetry_counters(runtime):
    telemetry.enable()
    s = DecodeScheduler(runtime)
    try:
        futs = [s.submit(_prompt(i), max_new_tokens=4, seed=i)
                for i in range(5)]
        [f.result(60) for f in futs]
    finally:
        s.close(drain=False, timeout=10.0)
    snap = telemetry.snapshot()
    c = snap["counters"]
    assert c["decode.requests"] == 5
    # an admission either prefills cold or skips via a full-prefix hit
    # (the module-scoped runtime's index may already know these prompts)
    assert c.get("decode.prefills", 0) + c.get("decode.prefill_skips", 0) \
        == 5
    assert c["decode.tokens"] == 20
    assert c["decode.evictions"] == 5
    ttft = snap["histograms"]["decode.ttft_ms"]
    assert ttft["count"] == 5 and ttft["sum"] > 0
    assert "decode.ttft_ms" not in c      # one type per metric family
    assert c.get("decode.compile_miss") in (None, 0)
    assert "decode.kv_occupancy" in snap["gauges"]
    assert "decode.kv_bytes_per_token" in snap["gauges"]


# ---------------------------------------- ISSUE 17: shared-prefix + int8
def _published_cache(**kw):
    """A small cache with one published 2-page prompt (chain + full
    entry, no tail: the prompt is page-aligned) and its donor slot."""
    cfg = dict(page_size=4, num_pages=12, max_pages_per_seq=4, max_slots=4)
    cfg.update(kw)
    c = PagedKVCache(2, 2, 16, **cfg)
    prompt = np.arange(1, 9, dtype="int32")            # 2 full pages
    donor = c.alloc(3, prompt=prompt)
    c.publish(donor, prompt, logits_row=np.zeros(7, "float32"))
    return c, prompt, donor


def test_prefix_sharing_refcounts_and_lifecycle():
    c, prompt, a = _published_cache()
    assert c.stats()["prefix_misses"] == 1
    b = c.alloc(3, prompt=prompt)                      # full hit
    assert b.shared_pages == 2
    assert b.pages[:2] == a.pages[:2]                  # acquired, not copied
    assert b.pages[2] not in a.pages
    assert b.prefix_logits is not None
    st = c.stats()
    assert st["prefix_hits"] == 1 and st["prefix_hit_rate"] == 0.5
    assert st["shared_pages"] >= 2
    # co-holder frees: shared pages survive for b AND for the index
    c.free(a)
    assert c.stats()["prefix_cached_pages"] == 2
    d = c.alloc(3, prompt=prompt)                      # still a hit
    assert d.shared_pages == 2
    c.free(b)
    c.free(d)
    # index pins keep the prefix warm with zero live slots
    assert c.pages_in_use == 0
    assert c.stats()["reclaimable_pages"] == 2
    c.drop_prefix_cache()
    assert c.stats()["prefix_cached_pages"] == 0
    assert c.stats()["reclaimable_pages"] == 0


def test_prefix_partial_chain_match_and_write_table():
    c, prompt, a = _published_cache()
    longer = np.concatenate([prompt, [9, 10, 11]]).astype("int32")
    b = c.alloc(4, prompt=longer)                      # chain match only
    assert b.shared_pages == 2 and b.prefix_logits is None
    wt = b.write_table()
    assert wt[:2] == [0, 0]                            # shared -> trash
    assert wt[2:4] == b.page_table[2:4] and 0 not in wt[2:4]
    c.free(b)
    c.free(a)


def test_prefix_cache_reclaimed_under_pressure():
    c, prompt, a = _published_cache()
    c.free(a)                                          # 2 pages pinned only
    assert c.stats()["reclaimable_pages"] == 2
    slots = [c.alloc(4), c.alloc(4)]                   # needs 8 of 11 usable
    big = c.alloc(3)                                   # forces reclaim
    assert c.stats()["prefix_cached_pages"] == 0       # index evicted LRU
    for s in slots + [big]:
        c.free(s)
    assert c.alloc(3, prompt=prompt).shared_pages == 0  # cold again
    # exhaustion message names the reclaimable count for pool sizing
    c2, _, a2 = _published_cache(num_pages=6)          # 5 usable, 3 held
    c2.free(a2)                                        # 2 pinned, 3 free... 
    c2.alloc(3)
    with pytest.raises(KVCacheExhausted) as ei:
        c2.alloc(4)                                    # > 2 free + 2 reclaim
    assert "reclaimable from the shared-prefix cache" in str(ei.value)


def _publish_and_free(c, prompt):
    """Publish ``prompt`` (chain + full entry) and leave its pages
    pinned-only; returns the donor's page list."""
    donor = c.alloc(len(prompt) // c.page_size, prompt=prompt)
    c.publish(donor, prompt, logits_row=np.zeros(7, "float32"))
    pages = list(donor.pages)
    c.free(donor)
    return pages


def test_prefix_hit_survives_reclaim_pressure():
    """Regression: a prefix-hit alloc under page pressure must never
    reclaim the pages it just matched — pre-fix the reclaimer freed the
    matched entry's pages (slot_refs still 0) and re-issued one as a
    writable fresh page, aliasing the shared prefix."""
    c = PagedKVCache(2, 2, 16, page_size=4, num_pages=8,
                     max_pages_per_seq=4, max_slots=4)
    p1 = np.arange(1, 9, dtype="int32")
    p2 = np.arange(101, 109, dtype="int32")
    _publish_and_free(c, p1)
    p2_pages = _publish_and_free(c, p2)
    blocker = c.alloc(3)                       # 0 free: hit must reclaim
    s = c.alloc(3, prompt=p2)                  # full hit on p2
    assert s.shared_pages == 2
    assert len(set(s.pages)) == len(s.pages)   # no page aliased
    assert s.pages[:2] == p2_pages[:2]         # matched pages kept intact
    # the matched entry survived reclaim (p1, the cold one, was evicted)
    assert c.stats()["prefix_cached_pages"] == 2
    c.free(s)
    c.free(blocker)
    assert c.alloc(3, prompt=p2).shared_pages == 2


def test_prefix_hit_exhausted_rolls_back():
    """When even reclaim can't free a fresh page, the hit path must roll
    back its acquisitions: the index stays intact and refcounts balance
    (pre-fix the matched pages were double-counted or freed)."""
    c = PagedKVCache(2, 2, 16, page_size=4, num_pages=6,
                     max_pages_per_seq=4, max_slots=4)
    p1 = np.arange(1, 9, dtype="int32")
    _publish_and_free(c, p1)
    blocker = c.alloc(3)                       # 0 free, 2 pinned by index
    with pytest.raises(KVCacheExhausted):
        c.alloc(3, prompt=p1)                  # hit, but no room for fresh
    assert c.stats()["prefix_cached_pages"] == 2   # index untouched
    c.free(blocker)
    s = c.alloc(3, prompt=p1)                  # retry after pressure: hit
    assert s.shared_pages == 2
    assert len(set(s.pages)) == len(s.pages)
    c.free(s)
    c.drop_prefix_cache()
    assert c.pages_in_use == 0
    assert all(r == 0 for r in c._slot_refs)   # refcounts balanced


def test_chain_eviction_unpublishes_suffix():
    """Evicting a chain link takes its whole suffix: links past a missing
    one can never match again, so leaving them pinned would strand pages
    in the index (pre-fix they held HBM invisibly)."""
    c = PagedKVCache(2, 2, 16, page_size=4, num_pages=6,
                     max_pages_per_seq=4, max_slots=4)
    prompt = np.arange(1, 13, dtype="int32")   # 3-page chain
    donor = c.alloc(3, prompt=prompt)
    c.publish(donor, prompt)                   # chain pins only, no entry
    c.free(donor)
    assert c.stats()["prefix_cached_pages"] == 3
    blocker = c.alloc(2)                       # 0 free
    s = c.alloc(1)                             # reclaim evicts the chain
    # the LRU head link went, and the rest of the chain went WITH it —
    # nothing is left pinned under unmatchable hashes
    assert c.stats()["prefix_cached_pages"] == 0
    assert c.stats()["reclaimable_pages"] == 0
    c.free(s)
    c.free(blocker)


def test_full_hit_keeps_chain_hot():
    """A full-entry hit must LRU-touch its chain hashes too: under later
    pressure the genuinely cold chain is evicted first, not the chain the
    hit just proved hot."""
    c = PagedKVCache(2, 2, 16, page_size=4, num_pages=10,
                     max_pages_per_seq=4, max_slots=4)
    p1 = np.arange(1, 9, dtype="int32")
    p2 = np.arange(101, 109, dtype="int32")
    _publish_and_free(c, p1)
    _publish_and_free(c, p2)
    hot = c.alloc(2, prompt=p1)                # full hit: p1 is hot now
    c.free(hot)
    blockers = [c.alloc(4), c.alloc(1)]        # 0 free
    trigger = c.alloc(2)                       # needs 2: evicts one chain
    c.free(trigger)
    for b in blockers:
        c.free(b)
    assert c.alloc(2, prompt=p1).shared_pages == 2   # hot chain survived
    assert c.alloc(2, prompt=p2).shared_pages == 0   # cold chain evicted


def test_stale_slot_sanitization_under_sharing():
    """The ISSUE 17 satellite: freeing one session of a shared prefix must
    NOT poison the survivor; the LAST free recycles (and poisons); a
    double free still raises."""
    c, prompt, a = _published_cache()
    with sanitizer.scope("slots"):
        b = c.alloc(3, prompt=prompt)
        c.check_slot(a)
        c.check_slot(b)
        c.free(a)                                      # co-holder leaves
        c.check_slot(b)                                # survivor is clean
        with pytest.raises(ValueError):
            c.free(a)                                  # double free raises
        c.drop_prefix_cache()                          # pins released too
        c.check_slot(b)                                # b still holds refs
        c.free(b)                                      # LAST holder: recycle
        with pytest.raises(StaleKVSlotError):
            c.check_slot(b)
        # page-level fence: a handle stamped before its page recycled
        # raises naming the page (defense in depth — the refcount
        # discipline makes this unreachable through the scheduler)
        d = c.alloc(1)
        d.page_gens[0] -= 1
        with pytest.raises(StaleKVSlotError) as ei:
            c.check_slot(d)
        assert ei.value.page == d.pages[0]
        c.free(d)
    sanitizer.reset()


def test_copy_on_write_divergence():
    """Two slots share a published prefix whose tail page is partial: each
    acquirer gets a private tail copy at admission (the CoW moment), so
    writes diverge without touching the donor's or the index's pages."""
    c = PagedKVCache(2, 2, 16, page_size=4, num_pages=12,
                     max_pages_per_seq=4, max_slots=4)
    prompt = np.arange(1, 7, dtype="int32")            # 1 full page + tail 2
    a = c.alloc(3, prompt=prompt)
    c.publish(a, prompt, logits_row=np.zeros(7, "float32"))
    before = c.cow_copies
    b = c.alloc(3, prompt=prompt)                      # full hit
    assert c.cow_copies == before + 1                  # eager tail copy
    assert b.pages[0] == a.pages[0]                    # chain page shared
    assert b.pages[1] != a.pages[1]                    # tail privatized
    # ensure_writable on the shared chain page forces a private copy
    c.ensure_writable(b, 0)
    assert b.pages[0] != a.pages[0] and b.shared_pages == 0
    # ...and on an exclusively-owned page it is a no-op
    p1 = b.pages[1]
    c.ensure_writable(b, 1)
    assert b.pages[1] == p1
    c.free(a)
    c.free(b)


def test_int8_quantize_roundtrip_row_stable():
    import jax.numpy as jnp
    from mxnet_tpu.serving.decode import kv_dequantize, kv_quantize_rows
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 7, 2, 16).astype("float32"))
    q, scale, mid = kv_quantize_rows(x)
    assert q.dtype == jnp.int8 and scale.shape == (4, 7)
    err = np.abs(np.asarray(kv_dequantize(q, scale, mid)) - np.asarray(x))
    rng_span = np.asarray(x.max(axis=(-2, -1)) - x.min(axis=(-2, -1)))
    assert (err <= rng_span[..., None, None] / 254.0 + 1e-6).all()
    # row stability: a row's codes don't depend on its neighbors
    q2, s2, m2 = kv_quantize_rows(x[1:3])
    assert (np.asarray(q2) == np.asarray(q[1:3])).all()
    assert (np.asarray(s2) == np.asarray(scale[1:3])).all()
    # all-zero rows (trash page) dequantize to exactly 0.0
    qz, sz, mz = kv_quantize_rows(jnp.zeros((1, 2, 16)))
    assert (np.asarray(kv_dequantize(qz, sz, mz)) == 0.0).all()


def test_int8_pool_geometry_doubles_admission():
    """The acceptance bar: at EQUAL pool bytes, int8 pools admit >= 2x the
    concurrent sequences of the fp32 baseline."""
    fp32 = PagedKVCache(2, 2, 16, page_size=8, num_pages=17,
                        max_pages_per_seq=4, max_slots=64)
    budget = fp32.usable_pages * fp32.page_bytes
    i8 = PagedKVCache(2, 2, 16, page_size=8,
                      num_pages=budget // (fp32.page_bytes // 3) + 1,
                      max_pages_per_seq=4, max_slots=64, kv_dtype="int8")
    assert i8.usable_pages * i8.page_bytes <= budget   # honest comparison
    assert i8.kv_bytes_per_token * 2 <= fp32.kv_bytes_per_token

    def max_admissible(cache, n_pages=2):
        held = []
        try:
            while True:
                held.append(cache.alloc(n_pages))
        except KVCacheExhausted:
            pass
        n = len(held)
        for s in held:
            cache.free(s)
        return n

    assert max_admissible(i8) >= 2 * max_admissible(fp32)


@pytest.fixture(scope="module")
def int8_session():
    net = get_decode_model("decode_tiny", vocab_size=VOCAB, max_length=32,
                           units=32, num_heads=2)
    net.initialize()
    from mxnet_tpu.serving.decode import DecodeSession
    sess = DecodeSession(net, batch_buckets=(1, 2), seq_buckets=(8, 16),
                         page_size=8, kv_dtype="int8")
    yield sess
    sess.close(drain=False)


def test_int8_session_deterministic_and_shared(int8_session):
    sess = int8_session
    assert sess.cache.quantized and sess.stats()["kv_dtype"] == "int8"
    p = _prompt(3, 6, 12)
    r1 = sess.generate(p, max_new_tokens=5, temperature=0.8, seed=4,
                       timeout=120)
    r2 = sess.generate(p, max_new_tokens=5, temperature=0.8, seed=4,
                       timeout=120)
    # quantization is elementwise-deterministic: the shared-vs-cold
    # bitwise contract holds in int8 too (r2 rode the prefix index)
    assert r1.token_ids == r2.token_ids
    assert sess.stats()["prefix_hits"] >= 1
    assert sess.cache.pages_in_use == 0


def test_shared_vs_cold_bitwise_across_joins(runtime):
    """The ISSUE 17 determinism bar: a request's tokens are bitwise
    identical whether its prefix was shared or cold, across continuous
    joins/evictions — checked against a prefix_sharing=False runtime."""
    sysp = _prompt(40, 10, 10)
    reqs = [dict(prompt=sysp + _prompt(50 + i, 1, 4),
                 max_new_tokens=3 + i % 4,
                 temperature=0.6 * (i % 2), seed=300 + i)
            for i in range(8)]
    # every third request repeats the bare system prompt with a fresh
    # seed: full-prefix hits that must still produce their own stream
    for i in (2, 5):
        reqs[i] = dict(prompt=sysp, max_new_tokens=4, temperature=0.9,
                       seed=400 + i)
    cold_rt = DecodeRuntime(runtime.block, batch_buckets=(1, 2, 4),
                            seq_buckets=(8, 16), page_size=8,
                            prefix_sharing=False)
    outs = {}
    for label, rt in (("shared", runtime), ("cold", cold_rt)):
        s = DecodeScheduler(rt)
        try:
            futs = []
            for i, r in enumerate(reqs):
                futs.append(s.submit(**r))
                time.sleep(0.002 * (i % 3))            # force joins
            outs[label] = [f.result(120).token_ids for f in futs]
        finally:
            s.close(drain=False, timeout=10.0)
    assert outs["shared"] == outs["cold"]
    assert cold_rt.cache.stats()["prefix_hits"] == 0   # genuinely cold
    assert runtime.cache.stats()["prefix_hits"] >= 2


def test_prefix_hit_skips_prefill_telemetry(runtime):
    telemetry.enable()
    s = DecodeScheduler(runtime)
    try:
        p = _prompt(60, 9, 9)
        s.generate(p, max_new_tokens=4, seed=1, timeout=60)
        # a successful prefix-hit admission counts as circuit-breaker
        # success exactly like a cold prefill does (max_new_tokens=1:
        # the request finishes at admission, so no decode step runs
        # that could reset the counter on the hit path's behalf)
        s._consecutive_failures = 1
        s.generate(p, max_new_tokens=1, seed=2, timeout=60)
        assert s._consecutive_failures == 0
    finally:
        s.close(drain=False, timeout=10.0)
    c = telemetry.snapshot()["counters"]
    assert c.get("decode.prefill_skips", 0) >= 1       # second skipped
    assert c.get("decode.prefix_hits", 0) >= 1
    assert c.get("decode.compile_miss") in (None, 0)   # fast path warmed


# -------------------------------------------------- fp8 KV pools (ISSUE 20)
def test_fp8_quantize_roundtrip_row_stable():
    import jax.numpy as jnp
    from mxnet_tpu.serving.decode import (kv_dequantize_fp8,
                                          kv_quantize_rows_fp8)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 7, 2, 16).astype("float32"))
    q, scale = kv_quantize_rows_fp8(x)
    assert q.dtype == jnp.float8_e4m3fn and scale.shape == (4, 7)
    xr = np.asarray(kv_dequantize_fp8(q, scale))
    xn = np.asarray(x)
    # e4m3 keeps 3 mantissa bits: relative error <= 2^-4 in the normal
    # range, absolute error bounded by the row scale in the subnormals
    err = np.abs(xr - xn)
    bound = np.abs(xn) / 16.0 + np.asarray(scale)[..., None, None] * 2e-3
    assert (err <= bound + 1e-7).all(), float((err - bound).max())
    # row stability: a row's codes don't depend on its neighbors
    q2, s2 = kv_quantize_rows_fp8(x[1:3])
    assert (np.asarray(q2).view("uint8")
            == np.asarray(q[1:3]).view("uint8")).all()
    assert (np.asarray(s2) == np.asarray(scale[1:3])).all()
    # all-zero rows (trash page) dequantize to exactly 0.0
    qz, sz = kv_quantize_rows_fp8(jnp.zeros((1, 2, 16)))
    assert (np.asarray(kv_dequantize_fp8(qz, sz)) == 0.0).all()


def test_fp8_pool_geometry_between_fp32_and_int8():
    """fp8 stores 1-byte values with ONE f32 sidecar row per pool
    (absmax scale — no midpoint), vs int8's two (scale + mid): fp8
    pages are strictly cheaper than int8 pages and far cheaper than
    fp32."""
    def mk(kvd):
        return PagedKVCache(2, 2, 16, page_size=8, num_pages=4,
                            max_pages_per_seq=2, max_slots=2,
                            kv_dtype=kvd)
    fp32, fp8, i8 = mk(None), mk("fp8_e4m3"), mk("int8")
    assert fp8.kv_bytes_per_token < fp32.kv_bytes_per_token
    assert fp8.num_sidecars == 2 and i8.num_sidecars == 4
    assert fp8.kv_bytes_per_token < i8.kv_bytes_per_token
    assert fp8.page_bytes < i8.page_bytes
    # the pools really are fp8
    import jax.numpy as jnp
    k_pool = fp8.pools[0]
    assert k_pool.dtype == jnp.float8_e4m3fn


@pytest.mark.parametrize("kv_dtype", ["float32", "int8", "fp8_e4m3"])
def test_pool_bytes_are_the_unpadded_rows(kv_dtype):
    """The pools hold exactly what ``kv_bytes_per_token`` promises: the
    row axis is ``heads * head_dim`` whole, no axis there to pad."""
    c = PagedKVCache(3, 4, 16, page_size=8, num_pages=5,
                     max_pages_per_seq=2, max_slots=2, kv_dtype=kv_dtype)
    assert c.k_pages.shape == (3, 5, 8, 4 * 16)
    assert sum(p.nbytes for p in c.pools) == \
        c.kv_bytes_per_token * c.page_size * c.num_pages


def test_aot_key_names_the_pool_geometry(tmp_path):
    """Two caches built from the same arguments whose pools differ in
    shape alone must not share compiled programs: the pools' shapes and
    dtypes are part of the AOT key, whichever module decides them."""
    net = get_decode_model("decode_tiny", vocab_size=VOCAB, max_length=32,
                           units=32, num_heads=2)
    net.initialize()

    def key(reshape):
        cache = PagedKVCache(2, 2, 16, page_size=8, num_pages=9,
                             max_pages_per_seq=4, max_slots=2)
        cache.set_pools(reshape(p) for p in cache.pools)
        rt = DecodeRuntime(net, cache=cache, batch_buckets=(1, 2),
                           seq_buckets=(8,), warm=False,
                           aot_cache=str(tmp_path))
        return rt.aot_cache.model_key

    flat = key(lambda p: p)
    assert flat == key(lambda p: p)
    assert flat != key(lambda p: p.reshape(p.shape[:3] + (2, 16)))
    assert flat != key(lambda p: p.astype("bfloat16"))


def test_fp8_commit_stores_the_prompt_in_fp8():
    """A prompt's K/V committed to fp8 pools reads back within e4m3's
    error of what the fp32 pools hold: the commit quantizes as the step
    does, by the pools' own sidecars."""
    from mxnet_tpu.serving.decode import kv_dequantize_fp8
    net = get_decode_model("decode_tiny", vocab_size=VOCAB, max_length=32,
                           units=32, num_heads=2)
    net.initialize()
    tokens = np.array([_prompt(7, 8, 8)], "int32")
    pools = {}
    for kvd in ("float32", "fp8_e4m3"):
        rt = DecodeRuntime(net, batch_buckets=(1,), seq_buckets=(8,),
                           page_size=8, kv_dtype=kvd, warm=False)
        rt.prefill(tokens, np.array([8], "int32"),
                   np.array([[1, 0, 0, 0]], "int32"),
                   np.zeros((1, 2), "uint32"), np.zeros((1,), "float32"))
        pools[kvd] = [np.asarray(p)[:, 1] for p in rt.cache.pools]
    for x, q, scale in zip(pools["float32"], pools["fp8_e4m3"][:2],
                           pools["fp8_e4m3"][2:]):
        assert np.abs(x).max() > 0
        back = np.asarray(kv_dequantize_fp8(
            q.reshape(q.shape[:-1] + (2, 16)), scale)).reshape(x.shape)
        bound = np.abs(x) / 16.0 + scale[..., None] * 2e-3
        assert (np.abs(back - x) <= bound + 1e-7).all()


@pytest.fixture(scope="module")
def fp8_session():
    net = get_decode_model("decode_tiny", vocab_size=VOCAB, max_length=32,
                           units=32, num_heads=2)
    net.initialize()
    from mxnet_tpu.serving.decode import DecodeSession
    sess = DecodeSession(net, batch_buckets=(1, 2), seq_buckets=(8, 16),
                         page_size=8, kv_dtype="fp8_e4m3")
    yield sess
    sess.close(drain=False)


def test_fp8_session_deterministic_and_shared(fp8_session):
    sess = fp8_session
    assert sess.cache.quantized and sess.stats()["kv_dtype"] == "fp8_e4m3"
    p = _prompt(3, 6, 12)
    r1 = sess.generate(p, max_new_tokens=5, temperature=0.8, seed=4,
                       timeout=120)
    r2 = sess.generate(p, max_new_tokens=5, temperature=0.8, seed=4,
                       timeout=120)
    # fp8 quantization is elementwise-deterministic: the shared-vs-cold
    # bitwise contract holds exactly like fp32/int8 (r2 rode the index)
    assert r1.token_ids == r2.token_ids
    assert sess.stats()["prefix_hits"] >= 1
    assert sess.cache.pages_in_use == 0


# ------------------------------------- speculative decoding (ISSUE 20)
from mxnet_tpu.serving.decode import (Drafter, NgramDrafter,  # noqa: E402
                                      SpecState)


@pytest.fixture(scope="module")
def spec_runtime():
    """One warmed speculative runtime (verify ladder k=3) shared by the
    whole speculative block — its own net so reference schedulers built
    on it are exactly comparable."""
    net = get_decode_model("decode_tiny", vocab_size=VOCAB, max_length=32,
                           units=32, num_heads=2)
    net.initialize()
    rt = DecodeRuntime(net, batch_buckets=(1, 2, 4), seq_buckets=(8, 16),
                       page_size=8, spec_buckets=(3,))
    yield rt


def _rep_prompt(i, n=9):
    """Motif-cycling prompt — the workload prompt-lookup drafting eats."""
    rng = np.random.RandomState(2000 + i)
    motif = list(rng.randint(1, VOCAB, 3))
    return (motif * ((n // 3) + 1))[:n]


def _spec_reqs(n=10):
    return [dict(prompt=_rep_prompt(i), max_new_tokens=4 + i % 5,
                 temperature=0.7 * (i % 3 == 0), seed=500 + i)
            for i in range(n)]


def _reference(spec_runtime, reqs):
    """Non-speculative streams from a drafterless scheduler on the SAME
    runtime (plain step programs, same weights)."""
    s = DecodeScheduler(spec_runtime)
    try:
        return [s.generate(timeout=120, **r).token_ids for r in reqs]
    finally:
        s.close(drain=False, timeout=10.0)


def test_spec_state_adapts_from_own_window():
    st = SpecState(2, 4)
    for _ in range(3):
        st.observe(2, 2)
    assert st.k == 2                      # needs >= 4 observations
    st.observe(2, 2)
    assert st.k == 3                      # hot window grows
    st.observe(3, 3)
    assert st.k == 4 and st.acceptance_rate == 1.0
    st.observe(4, 4)
    assert st.k == 4                      # capped at k_max
    cold = SpecState(3, 4)
    for _ in range(6):
        cold.observe(3, 0)
    assert cold.k == 1                    # shrinks, floors at 1
    cold.observe(0, 0)                    # zero-proposal rounds ignored
    assert cold.k == 1


def test_ngram_drafter_proposes_cycle_continuation():
    class R:
        prompt = np.array([5, 9, 2, 5, 9, 2, 5], "int32")
        tokens = []
    d = NgramDrafter()
    got = d.propose(R(), 3)
    assert got.tolist() == [9, 2, 5]      # continuation of latest [5]->...
    # longest suffix wins: trailing [2, 5] matches at position 2
    class R2:
        prompt = np.array([1, 2, 3, 4], "int32")
        tokens = []
    assert d.propose(R2(), 3).size == 0   # no repeat: no draft


def test_spec_continuous_and_solo_bitwise_with_zero_misses(spec_runtime):
    """THE tentpole contract: speculative streams — greedy and sampled,
    solo and continuous-batched, under donation+slots sanitizers — are
    bitwise the non-speculative streams, with zero steady-state compile
    misses and zero leaks."""
    reqs = _spec_reqs()
    ref = _reference(spec_runtime, reqs)
    spec_runtime.cache.drop_prefix_cache()
    s = DecodeScheduler(spec_runtime, drafter=NgramDrafter(), spec_k=3)
    try:
        with sanitizer.scope("donation,slots"):
            solo = [s.generate(timeout=120, **r).token_ids for r in reqs]
            assert solo == ref
            spec_runtime.cache.drop_prefix_cache()
            telemetry.enable()
            telemetry.reset()
            futs = []
            for i, r in enumerate(reqs):
                futs.append(s.submit(**r))
                time.sleep(0.002 * (i % 4))
            cont = [f.result(120).token_ids for f in futs]
            assert sanitizer.stats()["violations"] == 0
        snap = telemetry.snapshot()["counters"]
        telemetry.disable()
    finally:
        sanitizer.reset()
        s.close(drain=False, timeout=10.0)
    assert cont == ref
    assert not snap.get("decode.compile_miss"), snap
    assert snap.get("decode.spec_steps", 0) >= 1
    assert snap.get("decode.spec_accepted", 0) >= 1   # drafting worked
    assert spec_runtime.cache.pages_in_use == 0
    assert spec_runtime.cache.slots_in_use == 0


def test_spec_mixed_batch_with_non_spec_rows(spec_runtime):
    """Speculating and opted-out requests share the same boundary: the
    opted-out rows ride the verify with n_draft=0 (bitwise the plain
    step) and every stream still matches the non-spec reference."""
    reqs = _spec_reqs(8)
    ref = _reference(spec_runtime, reqs)
    spec_runtime.cache.drop_prefix_cache()
    s = DecodeScheduler(spec_runtime, drafter=NgramDrafter(), spec_k=3)
    try:
        futs = [s.submit(speculate=(i % 2 == 0), **r)
                for i, r in enumerate(reqs)]
        got = [f.result(120).token_ids for f in futs]
    finally:
        s.close(drain=False, timeout=10.0)
    assert got == ref


class _ScriptedDrafter(Drafter):
    """Drafts from a scripted continuation table (prompt tuple -> the
    known reference stream), optionally corrupted — the deterministic
    way to pin acceptance behavior."""

    name = "scripted"

    def __init__(self, table, corrupt=False, overshoot=False):
        self.table = table
        self.corrupt = corrupt
        self.overshoot = overshoot

    def propose(self, req, k):
        ref = self.table[tuple(int(t) for t in req.prompt)]
        done = len(req.tokens)
        if self.overshoot:
            k = k + 7          # deliberately ignore the budget cap
        cont = np.asarray(ref[done:done + k], "int32")
        if self.corrupt and cont.size:
            cont = (cont + 1) % VOCAB       # never equals the target
        return cont


def _table(reqs, ref):
    return {tuple(r["prompt"]): t for r, t in zip(reqs, ref)}


def test_spec_oracle_drafts_commit_bonus_tokens(spec_runtime):
    """All-accepted rounds commit k+1 tokens (the bonus) and finish in
    far fewer verify steps than tokens; rejected-at-position-0 rounds
    still emit exactly the target's token. Both streams stay bitwise."""
    reqs = _spec_reqs(4)
    ref = _reference(spec_runtime, reqs)
    spec_runtime.cache.drop_prefix_cache()
    telemetry.enable()
    for drafter, expect_accepts in (
            (_ScriptedDrafter(_table(reqs, ref)), True),
            (_ScriptedDrafter(_table(reqs, ref), corrupt=True), False)):
        telemetry.reset()
        s = DecodeScheduler(spec_runtime, drafter=drafter, spec_k=3)
        try:
            got = [s.generate(timeout=120, **r).token_ids for r in reqs]
        finally:
            s.close(drain=False, timeout=10.0)
        assert got == ref
        snap = telemetry.snapshot()["counters"]
        if expect_accepts:
            assert snap.get("decode.spec_bonus", 0) >= 1
            assert snap["decode.spec_accepted"] > 0
        else:
            # acceptance at position 0: every draft token mismatches,
            # every verify commits exactly one target token
            assert snap.get("decode.spec_accepted", 0) == 0
            assert snap.get("decode.spec_bonus", 0) == 0
        spec_runtime.cache.drop_prefix_cache()
    telemetry.disable()


def test_spec_draft_overshoot_is_budget_capped(spec_runtime):
    """A drafter ignoring its k (longer than the remaining budget) is
    truncated by the scheduler: writes stay inside the page
    reservation, the stream is exact, nothing leaks."""
    reqs = [dict(prompt=_rep_prompt(i), max_new_tokens=3,
                 temperature=0.0, seed=900 + i) for i in range(3)]
    ref = _reference(spec_runtime, reqs)
    spec_runtime.cache.drop_prefix_cache()
    s = DecodeScheduler(
        spec_runtime,
        drafter=_ScriptedDrafter(_table(reqs, ref), overshoot=True),
        spec_k=3)
    try:
        with sanitizer.scope("donation,slots"):
            got = [s.generate(timeout=120, **r).token_ids for r in reqs]
            assert sanitizer.stats()["violations"] == 0
    finally:
        sanitizer.reset()
        s.close(drain=False, timeout=10.0)
    assert got == ref
    assert all(len(t) == 3 for t in got)
    assert spec_runtime.cache.pages_in_use == 0


def test_spec_k0_budget_falls_back_to_plain_step(spec_runtime):
    """max_new_tokens=2 leaves zero draft budget after the first token
    (k <= max_new - generated - 1 = 0): the scheduler must run the
    plain step, not a degenerate verify."""
    reqs = [dict(prompt=_rep_prompt(i), max_new_tokens=2,
                 temperature=0.0, seed=950 + i) for i in range(3)]
    ref = _reference(spec_runtime, reqs)
    spec_runtime.cache.drop_prefix_cache()
    telemetry.enable()
    telemetry.reset()
    s = DecodeScheduler(spec_runtime, drafter=NgramDrafter(), spec_k=3)
    try:
        got = [s.generate(timeout=120, **r).token_ids for r in reqs]
    finally:
        s.close(drain=False, timeout=10.0)
    snap = telemetry.snapshot()["counters"]
    telemetry.disable()
    assert got == ref
    assert snap.get("decode.spec_steps", 0) == 0      # plain steps only
    assert snap.get("decode.steps", 0) >= 1


def test_spec_prefix_hit_session_speculates(spec_runtime):
    """A full-prompt prefix hit (admission IS the first token) must
    still enter speculative mode for its decode steps — and stay
    bitwise with the cold non-spec stream for the same (prompt, seed).
    The drafts are scripted from the reference stream: a sampled stream
    does not repeat its prompt's motif, so a prompt-lookup drafter would
    have nothing to propose and the verify steps counted below would
    depend on the weights' seed, not on the admission path."""
    p = _rep_prompt(7)
    kw = dict(max_new_tokens=6, temperature=0.8, seed=777)
    reqs = [dict(prompt=p, **kw)]
    ref = _reference(spec_runtime, reqs)
    spec_runtime.cache.drop_prefix_cache()
    telemetry.enable()
    telemetry.reset()
    s = DecodeScheduler(spec_runtime,
                        drafter=_ScriptedDrafter(_table(reqs, ref)),
                        spec_k=3)
    try:
        first = s.generate(p, timeout=120, **kw).token_ids   # publishes
        cold = dict(telemetry.snapshot()["counters"])
        hit = s.generate(p, timeout=120, **kw).token_ids     # prefix hit
    finally:
        s.close(drain=False, timeout=10.0)
    snap = telemetry.snapshot()["counters"]
    telemetry.disable()
    assert first == ref[0] and hit == ref[0]
    assert not cold.get("decode.prefix_hits") and \
        snap.get("decode.prefix_hits", 0) == 1
    # the hit's own decode steps were verify steps that accepted drafts
    for name in ("decode.spec_steps", "decode.spec_accepted"):
        assert snap.get(name, 0) > cold.get(name, 0) >= 1, name


def test_spec_drafter_failure_degrades_not_fails(spec_runtime):
    """Any drafter exception degrades the affected boundary/request to
    plain decode — requests never fail because a draft misfired."""
    class Exploding(Drafter):
        def __init__(self):
            self.calls = 0

        def propose_batch(self, reqs, ks):
            self.calls += 1
            raise RuntimeError("draft boom")

    reqs = _spec_reqs(3)
    ref = _reference(spec_runtime, reqs)
    spec_runtime.cache.drop_prefix_cache()
    d = Exploding()
    s = DecodeScheduler(spec_runtime, drafter=d, spec_k=3)
    try:
        got = [s.generate(timeout=120, **r).token_ids for r in reqs]
    finally:
        s.close(drain=False, timeout=10.0)
    assert got == ref and d.calls >= 1
    assert spec_runtime.cache.pages_in_use == 0


def test_spec_validation_errors(spec_runtime, runtime):
    with pytest.raises(ValueError, match="spec_buckets"):
        DecodeScheduler(runtime, drafter=NgramDrafter(), start=False)
    with pytest.raises(ValueError, match="spec_k"):
        DecodeScheduler(spec_runtime, drafter=NgramDrafter(), spec_k=9,
                        start=False)
    s = DecodeScheduler(spec_runtime)          # no drafter
    try:
        with pytest.raises(ValueError, match="no drafter"):
            s.submit(_rep_prompt(0), speculate=True)
    finally:
        s.close(drain=False, timeout=10.0)
    with pytest.raises(ValueError, match="unknown drafter"):
        DecodeScheduler(spec_runtime, drafter="nope", start=False)
