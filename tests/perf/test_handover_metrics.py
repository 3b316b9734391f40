"""The hand-over's metrics (PR 35): the bracket of the two clocks, span =
device + launch + wake on hand-built lines with a planted skew and on the
small trace recorded on a v5e, the rule that an empty bracket gives nothing,
each of the nine readers on hand-built observations (spans inside and outside
the profiler's seconds), what they say of a program whose spans carry no CPU
time, the entries in ``BENCHMARK.json``, and a traced rehearsal on the CPU."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf.harness import handover, readers, trace_reduce  # noqa: E402
from perf.harness.spec import Cell  # noqa: E402
from test_perf_harness import _args, _run, tiny_root  # noqa: E402,F401

CELL = "gpt2_medium.chat_paced"
SERVING = [CELL, "axk1_ep16.assist_steady", "nemotron3_nano_ep8.chat_steady",
           "mimo_v2_5_ep16.mixed_lengths"]
TOY = os.path.join(ROOT, "perf", "testdata", "toy_phases_v5e.xplane.pb")
TRACE = ("step_handover_p50_ms", "launch_p50_ms", "wake_p50_ms")
SPAN = ("dispatch_cpu_mean_ms", "dispatch_off_cpu_mean_ms",
        "sched_thread_cpu_share", "other_threads_cpu_cores",
        "profiler_step_stretch")
NEW = TRACE + SPAN + ("gateway_cpu_per_token_us",)
# two are listed only where this PR's own readings repeat (PERF.md section
# 7 (14), (15)): the other threads' cores not where one request at a time
# leaves the runtime's own threads alone, the profiler's stretch only where
# the batch is steady
LISTED = {"other_threads_cpu_cores": SERVING[1:],
          "profiler_step_stretch": SERVING[1:3]}
MS = 1e-3


# ------------------------------------------------------ the two lines, built
def _lines(skew_ms, launch_ms=(2.0, 1.0, 3.0, 1.5, 2.5, 2.0),
           wake_ms=(1.0, 2.0, 0.5, 1.5, 1.0, 2.0), device_ms=5.0,
           prefill_at=2):
    """A thread's ``decode.`` annotations and the first device's busy
    intervals for six calls (the third a prefill of two programs), the
    device's line running ``skew_ms`` AHEAD of the host's: every call
    launches ``launch_ms`` after its ``.dispatch`` opens and wakes
    ``wake_ms`` after its program's end."""
    host, busy, want, t = [], [], [], 0.010
    for k, (launch, wake) in enumerate(zip(launch_ms, wake_ms)):
        kind = "decode.prefill" if k == prefill_at else "decode.step"
        s = t + 1.0 * MS                    # admit and prepare come first
        d0 = s + launch * MS
        d1 = d0 + device_ms * MS
        e = d1 + wake * MS
        host += [("python", "decode.boundary", t, e + 0.5 * MS - t),
                 ("python", kind, s - 5e-6, e - s + 1e-5),
                 ("python", kind + ".dispatch", s, 0.7 * launch * MS),
                 ("python", kind + ".fetch", s + 0.7 * launch * MS,
                  e - s - 0.7 * launch * MS)]
        # a program is many operations; a prefill is two programs
        cuts = [d0, d0 + 1 * MS, d0 + 1.002 * MS, d0 + 3 * MS,
                d0 + (3.2 if kind == "decode.prefill" else 3.001) * MS, d1]
        busy += [[a - skew_ms * MS, b - skew_ms * MS]
                 for a, b in zip(cuts[::2], cuts[1::2])]
        want.append((kind, e - s, d1 - d0, launch * MS, wake * MS))
        t = e + 1.5 * MS
    # another thread's spans are not the loop's
    host.append(("other", "decode.step.dispatch", 0.0, 1.0))
    dev = {"/device:TPU:0": {"merged": busy, "busy_s": 0.0}}
    return trace_reduce.Reduced(dev, host, busy[0][0], busy[-1][1]), want


@pytest.mark.parametrize("skew_ms", [0.0, 1.2, -0.8, 2.5, -2.5])
def test_bracket_and_split_with_a_planted_skew(skew_ms):
    """The bracket holds the planted skew: it opens ``min launch`` under it
    and closes ``min wake`` over it, the window's first and last call
    aside.  The hand-over has no skew in it; launch and wake are right to
    half the bracket's width and add up to the hand-over call by call."""
    reduced, want = _lines(skew_ms)
    got = handover.by_call(reduced)
    inner = want[1:-1]
    assert [c["kind"] for c in got["calls"]] == [w[0] for w in inner]
    assert got["lo_s"] == pytest.approx((skew_ms - 1.0) * MS, abs=1e-9)
    assert got["hi_s"] == pytest.approx((skew_ms + 0.5) * MS, abs=1e-9)
    half = (got["hi_s"] - got["lo_s"]) / 2
    for c, (_kind, span, device, launch, wake) in zip(got["calls"], inner):
        assert c["span_s"] == pytest.approx(span, abs=1e-9)
        assert c["device_s"] == pytest.approx(device, abs=1e-9)
        assert c["handover_s"] == pytest.approx(launch + wake, abs=1e-9)
        assert c["launch_s"] + c["wake_s"] == pytest.approx(
            c["handover_s"], abs=1e-6 * MS)          # to a nanosecond
        assert abs(c["launch_s"] - launch) <= half + 1e-9
        assert abs(c["wake_s"] - wake) <= half + 1e-9


def test_an_empty_bracket_gives_nothing():
    """One program ends 0.15 ms after the fetch that awaited it returned,
    so the device's line is at least that far BEHIND; another starts 0.5 ms
    before its dispatch opened, so it is at least that far AHEAD.  No shift
    satisfies both: programs were given to the wrong spans, and every metric
    of the trace reads ``None``.  Either alone only narrows the bracket."""
    reduced, _want = _lines(0.0)
    dev = reduced.devices["/device:TPU:0"]
    late = [list(iv) for iv in dev["merged"]]
    for iv in late[9:12]:                    # the fourth call's operations
        iv[0] += 1.65 * MS
        iv[1] += 1.65 * MS
    dev["merged"] = late
    got = handover.by_call(reduced)
    assert (got["lo_s"], got["hi_s"]) == pytest.approx((-1.0 * MS, -.15 * MS))
    both = [list(iv) for iv in late]
    for iv in both[3:6]:                     # the second call's
        iv[0] -= 1.5 * MS
        iv[1] -= 1.5 * MS
    dev["merged"] = both
    assert handover.by_call(reduced) is None
    obs = {"cell": Cell(CELL), "trace": None}
    for name in TRACE:
        assert readers.read_metric(name, obs) is None


@pytest.mark.parametrize("drop", ["device", "loop", "fetch"])
def test_nothing_to_read_is_none(drop):
    reduced, _want = _lines(1.0)
    if drop == "device":
        reduced = trace_reduce.Reduced({}, reduced.host, 0.0, 1.0)
    elif drop == "loop":
        reduced.host = [h for h in reduced.host if h[1] != "decode.boundary"]
    else:       # a program from before the calls had children
        reduced.host = [h for h in reduced.host
                        if not h[1].endswith(".fetch")]
    assert handover.by_call(reduced) is None


def test_handover_on_the_recorded_tpu_trace():
    """``perf/testdata/toy_phases_v5e.xplane.pb``: four turns of one 5.8 ms
    program; the second and third are read.  On the device's line each
    program starts 0.7 to 1.2 ms BEFORE its dispatch span opens on the
    host's, so the bracket lies above zero; the hand-over needs no skew."""
    r = trace_reduce.reduce_trace(TOY, host_prefixes=("decode.",))
    assert [k for k, _s, _e in handover.calls(r)] == ["decode.step"] * 4
    got = handover.by_call(r)
    assert got["lo_s"] == pytest.approx(0.970938e-3, abs=1e-8)
    assert got["hi_s"] == pytest.approx(2.362871e-3, abs=1e-8)
    assert len(got["calls"]) == 2
    for c, (span, hand) in zip(got["calls"], [(7.21568e-3, 1.391933e-3),
                                              (7.51916e-3, 1.695408e-3)]):
        assert c["kind"] == "decode.step"
        assert c["device_s"] == pytest.approx(5.82375e-3, abs=1e-8)
        assert c["span_s"] == pytest.approx(span, abs=1e-8)
        assert c["handover_s"] == pytest.approx(hand, abs=1e-8)
        assert c["launch_s"] + c["wake_s"] == pytest.approx(
            c["handover_s"], abs=1e-9)
        assert c["launch_s"] >= 0 and c["wake_s"] >= 0


def test_trace_readers_share_one_reduction_and_print_it_once(
        monkeypatch, capsys):
    calls = []

    def reduce_again(path, host_prefixes=None, keep_host=20000):
        calls.append((path, host_prefixes))
        return _lines(1.2)[0]

    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: d + "/x.pb")
    monkeypatch.setattr(trace_reduce, "reduce_trace", reduce_again)
    cell = Cell(CELL)
    cell.root = "/checkout"
    obs = {"trace": object(), "cell": cell}
    got = {n: readers.read_metric(n, obs) for n in TRACE}
    # steps 2, 4, 5 of the six calls (the third is a prefill): hand-overs
    # 3.0, 3.0, 3.5 ms; launches 1.0, 1.5, 2.5 and wakes 2.0, 1.5, 1.0,
    # each read 0.25 ms off at the bracket's middle, 0.95 ms for 1.2
    assert got["step_handover_p50_ms"] == pytest.approx(3.0)
    assert got["launch_p50_ms"] == pytest.approx(1.5 - 0.25)
    assert got["wake_p50_ms"] == pytest.approx(1.5 + 0.25)
    assert calls == [("/checkout/.perf_out/trace/x.pb", ("decode.",))]
    out = capsys.readouterr().out.splitlines()
    assert sum(ln.startswith("device_clock_ahead_ms_bracket [0.2000, 1.7000]")
               for ln in out) == 1
    (line,) = [ln for ln in out if ln.startswith("handover_by_kind ")]
    kinds = json.loads(line[len("handover_by_kind "):])
    assert kinds["decode.step"]["n"] == 3 and kinds["decode.prefill"] == {
        "n": 1, "span_p50_ms": 8.5, "device_p50_ms": 5.0,
        "handover_p50_ms": 3.5, "launch_p50_ms": 2.75, "wake_p50_ms": 0.75}


# ------------------------------------------------- the host clock's readers
def _turns(t, n, step_ms, dispatch_ms, cpu_ms, others_ms, attrs=True):
    """``n`` turns from ``t``: a boundary of ``step_ms + 1`` around a step
    of ``step_ms`` whose dispatch takes ``dispatch_ms`` of which ``cpu_ms``
    on the CPU; every other thread burns ``others_ms`` a turn."""
    out = []
    for k in range(n):
        at = t + k * (step_ms + 2) * MS
        cpu = {"cpu_ms": cpu_ms + 0.5} if attrs else {}
        proc = dict(cpu, proc_cpu_ms=cpu_ms + 0.5 + others_ms) \
            if attrs else {}
        out += [("decode.boundary", at, (step_ms + 1) * MS, proc),
                ("decode.step", at + 0.5 * MS, step_ms * MS, {"batch": 1}),
                ("decode.step.dispatch", at + 0.5 * MS, dispatch_ms * MS,
                 {"cpu_ms": cpu_ms} if attrs else {})]
    return out


def _span_obs(attrs=True):
    """A window that opens at 100.0 on the bus's clock; the cell's
    profiler runs from second 15 for 5: turns at 2-3 s and 30-31 s are
    outside it, at 17 s inside it (slower, and carrying other numbers), at
    14 s and 21 s nearer than 1.5 s to its edges."""
    spans = [("decode.idle", 100.0, 1.0, {})]
    spans += _turns(102.0, 20, 10.0, 3.0, 0.4, 12.0, attrs)
    spans += _turns(130.0, 20, 10.0, 5.0, 0.6, 8.0, attrs)
    spans += _turns(117.0, 20, 15.0, 9.0, 2.0, 30.0, attrs)
    spans += _turns(114.0, 3, 40.0, 30.0, 9.0, 90.0, attrs)
    spans += _turns(121.0, 3, 40.0, 30.0, 9.0, 90.0, attrs)
    return {"spans": spans, "cell": Cell(CELL), "trace": None,
            "values": {"window_wall_s": 52.0},
            "counters": {"gateway.handler_cpu_ms": 260.0,
                         "decode.tokens": 130.0}}


def test_the_profilers_seconds_on_the_bus_clock():
    obs = _span_obs()
    assert Cell(CELL).traffic["trace_window_s"] == [15.0, 5.0]
    assert handover.profiler_seconds(obs) == (115.0, 120.0)
    # a window too short for the start that the file names: the driver
    # moves the start to the middle of what is left
    obs["values"]["window_wall_s"] = 9.0
    assert handover.profiler_seconds(obs) == (102.0, 107.0)
    assert handover.profiler_seconds(dict(obs, spans=[])) is None
    assert handover.profiler_seconds(dict(obs, values={})) is None
    assert len(handover.outside_profiler(_span_obs(), "decode.step")) == 40
    assert len(handover.outside_profiler(
        _span_obs(), "decode.step", inside=True)) == 20


@pytest.mark.parametrize("name,want", [
    # 20 spans of 3 ms with 0.4 on the CPU, 20 of 5 with 0.6: the means
    ("dispatch_cpu_mean_ms", 0.5),
    ("dispatch_off_cpu_mean_ms", 3.5),
    ("sched_thread_cpu_share", 100 * 1.0 / 11.0),   # 0.9 and 1.1 of 11 ms
    ("other_threads_cpu_cores", 10.0 / 11.0),   # 12 and 8 ms of 11 ms
    ("profiler_step_stretch", 50.0),            # 15 ms inside, 10 outside
    ("gateway_cpu_per_token_us", 2000.0)])      # 260 ms over 130 tokens
def test_readers_on_hand_built_spans(name, want):
    assert readers.read_metric(name, _span_obs()) == pytest.approx(want)


def test_a_cpu_clock_that_ticks_still_splits_the_mean_span():
    """The chip's host: a span's ``cpu_ms`` reads 0 or a whole tick of 10 ms,
    so the median of ``cpu_ms`` is 0 whatever a launch costs.  The sum over
    all the spans is unbiased: of the 40 launches outside the profiler's
    seconds (160 ms together) every tenth reads a tick, 40 ms, a quarter;
    the mean span of 4 ms is 1 ms of CPU and 3 off it."""
    obs = _span_obs()
    launches = [a for n, _s, _d, a in obs["spans"]
                if n == "decode.step.dispatch"]
    for k, attrs in enumerate(launches):
        attrs["cpu_ms"] = 10.0 if k % 10 == 0 else 0.0
    assert readers.read_metric("dispatch_cpu_mean_ms", obs) == \
        pytest.approx(1.0)
    assert readers.read_metric("dispatch_off_cpu_mean_ms", obs) == \
        pytest.approx(3.0)


def test_people_lines_print_once(capsys):
    obs = _span_obs()
    for name in SPAN + SPAN:
        readers.read_metric(name, obs)
    out = capsys.readouterr().out.splitlines()
    (line,) = [ln for ln in out
               if ln.startswith("span_by_clock decode.step.dispatch ")]
    got = json.loads(line.split(" ", 2)[2])
    assert got["n"] == 40 and got["ticks"] == 50
    assert got["dur_mean_ms"] == pytest.approx(
        got["cpu_mean_ms"] + got["off_cpu_mean_ms"])
    assert (got["dur_p50_ms"], got["cpu_share"], got["least_cpu_ms"]) == (
        4.0, 0.125, 0.4)
    (line,) = [ln for ln in out if ln.startswith("cpu_cores ")]
    got = json.loads(line[len("cpu_cores "):])
    assert got == {"turns": 40, "scheduler": 0.0909, "others": 0.9091,
                   "door": 0.005, "rest": 0.9041}


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_in_a_program_without_the_attrs(name):
    """The parent's program: its spans carry no CPU time, it counts nothing
    at the door, and this run has no trace.  No reader raises; only the
    stretch, which reads durations alone, gives a number."""
    obs = _span_obs(attrs=False)
    obs["counters"].pop("gateway.handler_cpu_ms")
    got = readers.read_metric(name, obs)
    assert (got == pytest.approx(50.0)) if name == "profiler_step_stretch" \
        else got is None
    # and a cell whose traffic names no profiler seconds reads nothing
    obs = _span_obs()
    obs["cell"].traffic.pop("trace_window_s")
    if name in SPAN:
        assert readers.read_metric(name, obs) is None


# ------------------------------------------------------------- the entries
@pytest.mark.parametrize("name", NEW)
def test_entry_is_in_the_benchmark_for_the_serving_cells(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    (m,) = [m for m in per_layer if m["name"] == name]
    listed = LISTED.get(name, SERVING)
    assert m["workloads"] == listed and m["moves"] == "tpot_p50_ms"
    assert m["better"] == "lower"
    assert m["source"] == ("device_trace" if name in TRACE else
                           "program_counter" if name.startswith("gateway_")
                           else "program_span")
    assert name not in [
        x["name"] for x in Cell("bert_base.pretrain_s512").per_layer()]
    for cell in SERVING:
        assert (name in [x["name"] for x in Cell(cell).per_layer()]) == (
            cell in listed)


def test_traced_rehearsal_reads_the_host_clock_metrics_on_the_cpu(
        tiny_root):  # noqa: F811
    """The tiny server, traced on the CPU for its first tenth of a second:
    the four metrics of the host's clock that the cell lists have values, the
    three that need a device's line are left out, and every older metric is
    still there."""
    path = os.path.join(tiny_root, "perf", "traffic", "chat_paced.json")
    with open(path) as f:
        tr = json.load(f)
    tr["trace_window_s"] = [0.0, 0.1]
    with open(path, "w") as f:
        json.dump(tr, f)
    _cell, out, line = _run(tiny_root, CELL,
                            _args(seed=2**31 + 35, seconds=4.0, trace=1))
    got = line["metrics"]
    assert set(SPAN[:3]) | {"gateway_cpu_per_token_us"} <= set(got)
    assert not (set(TRACE) | set(LISTED)) & set(got)
    assert {"step_span_p50_ms", "loop_host_p50_ms", "rows_per_step"} \
        <= set(got)
    cpu = got["dispatch_cpu_mean_ms"]["value"]
    wait = got["dispatch_off_cpu_mean_ms"]["value"]
    assert 0 < cpu and -0.05 <= wait and cpu + wait < \
        got["step_span_p50_ms"]["value"] * 3
    assert 0 < got["sched_thread_cpu_share"]["value"] <= 101
    assert 0 < got["gateway_cpu_per_token_us"]["value"] < 1e6
    steps = [a for n, _s, _d, a in out["obs"]["spans"]
             if n == "decode.step.dispatch"]
    assert steps and all("cpu_ms" in a for a in steps)
