"""The decode loop's pipeline of depth one (ISSUE 36): step n+1 is launched
before step n's tokens are collected, the tokens going from one program to
the next on the device.

Every scripted run here drives ``DecodeScheduler._boundary`` by hand on an
unstarted scheduler, so that which turn launches ahead and which is
synchronous is the script's and not a race's.  The reference streams come
from the runtime's synchronous ``prefill`` + ``step`` on one request at a
time: the model's math is row-stable, so a request's stream is the same
solo, in a batch, and whichever side its step's tokens came from.
"""
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.analysis import sanitizer
from mxnet_tpu.resilience import faults
from mxnet_tpu.resilience.faults import InjectedFault
from mxnet_tpu.serving import RequestRejected
from mxnet_tpu.serving.decode import (DecodeRuntime, DecodeScheduler,
                                      PagedKVCache, get_decode_model,
                                      pages_needed)
from mxnet_tpu.serving.decode.speculate import Drafter

VOCAB = 61


@pytest.fixture(autouse=True)
def _clean_bus():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


def _net():
    net = get_decode_model("decode_tiny", vocab_size=VOCAB, max_length=32,
                           units=32, num_heads=2)
    net.initialize()
    return net


@pytest.fixture(scope="module")
def runtime():
    """One warmed runtime for the module: prefix sharing off, so that a
    second pass over the same prompts prefills cold like the first."""
    yield DecodeRuntime(_net(), batch_buckets=(1, 2, 4), seq_buckets=(8, 16),
                        page_size=8, prefix_sharing=False,
                        spec_buckets=(3,))


def _prompt(i, n=5):
    return list(np.random.RandomState(3000 + i).randint(1, VOCAB, n))


def _solo(rt, prompt, max_new_tokens, temperature=0.0, seed=0, eos_id=None):
    """One request through the runtime's SYNCHRONOUS calls, alone in the
    one-row programs: what the scheduler's stream has to be."""
    prompt = np.asarray(prompt, "int32")
    cache = rt.cache
    slot = cache.alloc(pages_needed(prompt.size, max_new_tokens,
                                    cache.page_size))
    try:
        s = rt.seq_bucket_for(prompt.size)
        tokens = np.zeros((1, s), "int32")
        tokens[0, :prompt.size] = prompt
        seed &= 0xffffffffffffffff
        key = np.array([[seed >> 32, seed & 0xffffffff]], "uint32")
        temp = np.array([temperature], "float32")
        first, _ = rt.prefill(tokens, np.array([prompt.size], "int32"),
                              np.asarray(slot.write_table(), "int32")[None],
                              key, temp)
        out = [int(first[0])]
        while len(out) < max_new_tokens and out[-1] != eos_id:
            nxt = rt.step(np.array([out[-1]], "int32"),
                          np.array([prompt.size + len(out) - 1], "int32"),
                          np.asarray(slot.page_table, "int32")[None], key,
                          np.array([len(out)], "int32"), temp)
            out.append(int(nxt[0]))
        return out
    finally:
        cache.free(slot)


def _settle(s, turns=200):
    """Boundaries until the scheduler has nothing left to do."""
    for _ in range(turns):
        if not s._queue and not s._running():
            return
        s._boundary()
    raise AssertionError("the scheduler did not settle")


class _Launches:
    """The runtime's launches, each as ``(rows of the program, tokens from
    the host)``: a host array is a synchronous turn's, a device array one
    launched ahead of the collect."""

    def __init__(self, rt, monkeypatch):
        self.seen = []
        launch = rt.launch

        def noted(tokens, *rest):
            self.seen.append((tokens.shape[0],
                              isinstance(tokens, np.ndarray)))
            return launch(tokens, *rest)

        monkeypatch.setattr(rt, "launch", noted)

    @property
    def host(self):
        return sum(host for _b, host in self.seen)

    @property
    def device(self):
        return len(self.seen) - self.host


# ------------------------------------------------------ (a) the same streams
@pytest.mark.parametrize("temperature", [0.0, 0.8],
                         ids=["greedy", "sampled"])
def test_mixed_batch_streams_are_the_synchronous_ones(runtime, monkeypatch,
                                                      temperature):
    """Different ``max_new``, one row that ends by ``eos_id`` mid-batch, one
    join mid-flight and one ``cancel()``: token for token the synchronous
    streams, and most steps were launched ahead."""
    rt = runtime
    kw = [dict(prompt=_prompt(0), max_new_tokens=9, seed=10),
          dict(prompt=_prompt(1), max_new_tokens=4, seed=11),
          dict(prompt=_prompt(2), max_new_tokens=9, seed=12),
          dict(prompt=_prompt(3, 7), max_new_tokens=6, seed=13),   # joins
          dict(prompt=_prompt(4), max_new_tokens=9, seed=14)]      # cancelled
    for k in kw:
        k["temperature"] = temperature
    ref = [_solo(rt, **k) for k in kw]
    # the third request stops at the first token of its stream that it has
    # not produced before: mid-batch, one step before the host knows
    eos_at = next(i for i in range(2, 8) if ref[2][i] not in ref[2][:i])
    kw[2]["eos_id"] = ref[2][eos_at]
    ref[2] = _solo(rt, **kw[2])
    assert ref[2] == ref[2][:eos_at + 1] and len(ref[2]) < 9

    counts = _Launches(rt, monkeypatch)
    s = DecodeScheduler(rt, start=False)
    try:
        futs = [s.submit(**k) for k in kw[:3]]
        stream = s.stream(**kw[4])
        s._boundary()                     # prefills, the first step launched
        s._boundary()                     # the second ahead of its collect
        assert s._flying is not None and counts.device == 1
        futs.append(s.submit(**kw[3]))    # joins behind the step in flight
        s._boundary()
        assert stream.cancel()
        _settle(s)
        got = [f.result(0) for f in futs]
    finally:
        s.close(drain=False, timeout=10.0)
    assert [g.token_ids for g in got] == ref[:4]
    assert [g.finish_reason for g in got] == ["length", "length", "eos",
                                              "length"]
    seen = list(stream._pending)
    assert seen and seen == ref[4][:len(seen)] and len(seen) < 9
    assert counts.device > counts.host
    assert rt.cache.pages_in_use == 0 and rt.cache.slots_in_use == 0


# ------------------------------------------- (b) the eos row's slot and pages
def test_eos_row_frees_its_slot_once_and_its_pages_are_reused(monkeypatch):
    """A row that ends by ``eos_id`` rides one step more than its stream
    has tokens.  Its slot is freed once, behind that launch, and the next
    admission takes its pages: with room for two sequences only, the third
    request can have no others."""
    net = _net()
    cache = PagedKVCache(net.num_layers, net.num_heads, net.head_dim,
                         page_size=4, num_pages=9, max_pages_per_seq=4,
                         max_slots=2, prefix_sharing=False)
    rt = DecodeRuntime(net, cache=cache, batch_buckets=(1, 2),
                       seq_buckets=(8,))
    a = dict(prompt=_prompt(5), max_new_tokens=10, seed=20, temperature=0.9)
    b = dict(prompt=_prompt(6), max_new_tokens=10, seed=21)
    c = dict(prompt=_prompt(7), max_new_tokens=10, seed=22, temperature=0.9)
    ref = [_solo(rt, **k) for k in (a, b, c)]
    eos_at = next(i for i in range(2, 8) if ref[0][i] not in ref[0][:i])
    a["eos_id"] = ref[0][eos_at]
    freed = []
    free = cache.free
    monkeypatch.setattr(
        cache, "free",
        lambda slot: (freed.append((slot, set(slot.pages))), free(slot))[1])
    s = DecodeScheduler(rt, start=False)
    try:
        with sanitizer.scope("donation,slots"):
            fa, fb = s.submit(**a), s.submit(**b)
            fc = None
            for _ in range(60):
                s._boundary()
                if fa.done() and fc is None:
                    # the step after its last is in flight, with its row
                    assert s._flying is not None
                    assert any(r is not None and r.future is fa
                               for r in s._flying.rows)
                    a_pages = freed[0][1]
                    fc = s.submit(**c)
                    s._boundary()
                    (c_req,) = [r for r in s._active if r.future is fc]
                    assert a_pages & set(c_req.slot.pages)
                if fc is not None and fc.done() and fb.done():
                    break
            assert sanitizer.stats()["violations"] == 0
    finally:
        sanitizer.reset()
        s.close(drain=False, timeout=10.0)
    assert fa.result(0).token_ids == ref[0][:eos_at + 1]
    assert fa.result(0).finish_reason == "eos"
    assert fb.result(0).token_ids == ref[1]
    assert fc.result(0).token_ids == ref[2]
    assert len(freed) == 3 and len({id(slot) for slot, _p in freed}) == 3
    assert cache.pages_in_use == 0 and cache.slots_in_use == 0


# ------------------------------------------------------------- (c) failures
def _two_in_flight(rt):
    """A scheduler with two requests decoding and a step in flight that was
    launched ahead."""
    s = DecodeScheduler(rt, start=False, breaker_threshold=None)
    futs = [s.submit(_prompt(i), max_new_tokens=12, seed=i) for i in (8, 9)]
    s._boundary()
    s._boundary()
    assert s._flying is not None and len(s._active) == 2
    return s, futs


@pytest.mark.parametrize("where", ["fault_before_the_launch",
                                   "exception_at_the_collect"])
def test_a_failure_fails_the_rows_of_both_steps_once(runtime, monkeypatch,
                                                     where):
    s, futs = _two_in_flight(runtime)
    evicted = []
    evict = s._evict
    monkeypatch.setattr(
        s, "_evict", lambda req, reason: (evicted.append((req, reason)),
                                          evict(req, reason))[1])
    try:
        if where == "fault_before_the_launch":
            with faults.scope("decode.step:fail"):
                s._boundary()
            error = InjectedFault
        else:
            def broken(flight):
                raise RuntimeError("the program failed on the device")
            with monkeypatch.context() as m:
                m.setattr(runtime, "collect", broken)
                s._boundary()
            error = RuntimeError
        for f in futs:
            with pytest.raises(error):
                f.result(0)
        assert s.steps_failed == 1
        assert s._flying is None and not s._active
        assert [reason for _req, reason in evicted] == ["failed", "failed"]
        assert runtime.cache.pages_in_use == 0
        assert runtime.cache.slots_in_use == 0
        # the loop goes on: the next request is served whole
        ok = s.submit(_prompt(8), max_new_tokens=5, seed=8)
        _settle(s)
        assert ok.result(0).token_ids == _solo(runtime, _prompt(8), 5,
                                               seed=8)
        assert s.steps_failed == 1
    finally:
        s.close(drain=False, timeout=10.0)


def test_a_collect_failure_leaves_the_worker_alive(runtime, monkeypatch):
    """The same under the live worker: the third collect raises, with the
    fourth step already behind it."""
    calls = []
    collect = runtime.collect

    def third_fails(flight):
        calls.append(flight)
        if len(calls) == 3:
            raise RuntimeError("the program failed on the device")
        return collect(flight)

    monkeypatch.setattr(runtime, "collect", third_fails)
    s = DecodeScheduler(runtime, breaker_threshold=None)
    try:
        f = s.submit(_prompt(8), max_new_tokens=12, seed=8)
        with pytest.raises(RuntimeError):
            f.result(60)
        ok = s.generate(_prompt(9), max_new_tokens=4, seed=9, timeout=60)
        assert len(ok.token_ids) == 4
        assert s.steps_failed == 1 and s.worker_restarts == 0
        assert s._worker.is_alive()
    finally:
        s.close(drain=False, timeout=10.0)
    assert runtime.cache.pages_in_use == 0


# --------------------------------------------------- (d) verify turns are not
class _Scripted(Drafter):
    """Drafts each request's known continuation."""

    def __init__(self, table):
        self.table = table

    def propose(self, req, k):
        ref = self.table[tuple(int(t) for t in req.prompt)]
        return np.asarray(ref[len(req.tokens):len(req.tokens) + k], "int32")


def test_verify_turns_are_never_launched_ahead(runtime, monkeypatch):
    """A drafter-bound scheduler: nothing is in flight when a verify is
    launched, no plain step of a speculating row is launched on the
    device's tokens, and the streams are the plain ones.  A row that opted
    out of speculation alone in the batch is pipelined like any other."""
    rt = runtime
    kw = [dict(prompt=_prompt(20 + i, 6), max_new_tokens=5 + i % 4,
               temperature=0.7 * (i % 2), seed=40 + i) for i in range(5)]
    ref = [_solo(rt, **k) for k in kw]
    in_flight = []
    verify = rt.verify
    s = DecodeScheduler(
        rt, start=False, spec_k=3,
        drafter=_Scripted({tuple(k["prompt"]): r for k, r in zip(kw, ref)}))
    monkeypatch.setattr(
        rt, "verify",
        lambda *a: (in_flight.append(s._flying), verify(*a))[1])
    counts = _Launches(rt, monkeypatch)
    telemetry.enable()
    try:
        futs = [s.submit(**k) for k in kw[:3]]
        s._boundary()
        futs += [s.submit(**k) for k in kw[3:]]
        _settle(s)
        assert [f.result(0).token_ids for f in futs] == ref
        c = telemetry.snapshot()["counters"]
        assert c["decode.spec_steps"] >= 3 and in_flight
        assert all(flying is None for flying in in_flight)
        assert counts.device == 0 and not c.get("decode.steps_ahead")
        plain = s.submit(speculate=False, **kw[0])
        _settle(s)
        assert plain.result(0).token_ids == ref[0]
        assert counts.device >= 2
    finally:
        telemetry.disable()
        s.close(drain=False, timeout=10.0)
    assert rt.cache.pages_in_use == 0


# ------------------------------------------------------- (e) the counter
def test_steps_ahead_is_steps_less_the_synchronous_turns(runtime,
                                                         monkeypatch):
    """Two rows of six tokens and a join of three: step 1 starts the
    pipeline and step 4 takes the joining row's first token from the host;
    steps 2, 3 and 5 are launched ahead."""
    rt = runtime
    counts = _Launches(rt, monkeypatch)
    telemetry.enable()
    s = DecodeScheduler(rt, start=False)
    try:
        futs = [s.submit(_prompt(i), max_new_tokens=6, seed=i)
                for i in (30, 31)]
        for _ in range(3):
            s._boundary()
        futs.append(s.submit(_prompt(32), max_new_tokens=3, seed=32))
        _settle(s)
        assert [len(f.result(0).token_ids) for f in futs] == [6, 6, 3]
    finally:
        s.close(drain=False, timeout=10.0)
    c = telemetry.snapshot()["counters"]
    telemetry.disable()
    assert c["decode.steps"] == 5
    assert (counts.host, counts.device) == (2, 3)
    assert c["decode.steps_ahead"] == c["decode.steps"] - counts.host
    assert c["decode.tokens"] == 15 and c["decode.joins"] == 1
    assert not c.get("decode.compile_miss")


# --------------------------------------------------------------- (f) close
@pytest.mark.parametrize("drain", [True, False], ids=["drain", "no_drain"])
def test_close_with_a_step_in_flight_leaves_nothing_pending(runtime, drain):
    s, futs = _two_in_flight(runtime)
    s.close(drain=drain, timeout=30.0)
    assert all(f.done() for f in futs)
    assert s._flying is None and not s._active and not s._queue
    if drain:
        assert [f.result(0).token_ids for f in futs] == [
            _solo(runtime, _prompt(i), 12, seed=i) for i in (8, 9)]
    else:
        for f in futs:
            with pytest.raises(RequestRejected) as ei:
                f.result(0)
            assert ei.value.reason == "shutdown"
    assert runtime.cache.pages_in_use == 0
    assert runtime.cache.slots_in_use == 0


def test_a_place_left_rides_padded_until_a_smaller_program_would_do(runtime,
                                                                    monkeypatch):
    """Four rows in the four-row program; the one that reaches its length
    leaves a padded place and the pipeline goes on; when two are left the
    two-row program would do, and that turn is a synchronous one."""
    rt = runtime
    kw = [dict(prompt=_prompt(40 + i), max_new_tokens=m, seed=50 + i)
          for i, m in enumerate((4, 8, 6, 8))]
    ref = [_solo(rt, **k) for k in kw]
    launched = _Launches(rt, monkeypatch).seen
    s = DecodeScheduler(rt, start=False)
    try:
        futs = [s.submit(**k) for k in kw]
        _settle(s)
        assert [f.result(0).token_ids for f in futs] == ref
    finally:
        s.close(drain=False, timeout=10.0)
    # (rows of the program, tokens from the host): steps 1-3 carry four
    # rows, 4-5 three with one place padded, 6-7 the two that are left
    assert launched == [(4, True)] + [(4, False)] * 4 + [(2, True),
                                                         (2, False)]
