"""The per-layer metrics that read the program's own spans (PR 24): each of
the six readers on hand-built observations, the idle-by-phase arithmetic on
hand-built intervals and on a small trace recorded on a v5e
(``perf/testdata/toy_phases_v5e.xplane.pb``, by ``perf/tools/phase_probe.py``),
what the readers say of a program that has no such spans, and a tiny-size
traced rehearsal on the CPU."""
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf.harness import idle_phases, readers, trace_reduce  # noqa: E402
from perf.harness.spec import Cell  # noqa: E402
from test_perf_harness import _args, _run, tiny_root  # noqa: E402,F401

CELL = "gpt2_medium.chat_paced"
TOY = os.path.join(ROOT, "perf", "testdata", "toy_phases_v5e.xplane.pb")
NEW = ("step_span_p50_ms", "loop_host_p50_ms", "sched_queue_wait_mean_ms",
       "first_frame_egress_mean_ms", "idle_host_loop_share",
       "idle_no_work_share")


def _turn(k, t, boundary_ms, calls):
    """Bus spans ``(name, start_s, dur_s, attrs)`` of one turn of the loop:
    a boundary of ``boundary_ms`` whose children are ``calls``
    ``[(name, ms, attrs)]`` laid end to end from its start."""
    bid = 1000 + k
    out = [("decode.boundary", t, boundary_ms / 1e3,
            {"trace_id": k, "span_id": bid, "parent_id": k, "active": 1})]
    at = t
    for i, (name, ms, attrs) in enumerate(calls):
        out.append((name, at, ms / 1e3, dict(
            attrs, trace_id=k, span_id=bid * 10 + i, parent_id=bid)))
        at += ms / 1e3
    return out


def _span_obs():
    spans = []
    # three turns: a plain step, a join (prefill + step), a plain step
    spans += _turn(1, 0.00, 60.0, [("decode.admit", 0.5, {}),
                                   ("decode.step", 57.0, {"batch": 1})])
    spans += _turn(2, 0.10, 200.0, [("decode.prefill", 130.0,
                                     {"batch": 1, "seq": 64}),
                                    ("decode.step", 59.0, {"batch": 2}),
                                    ("decode.step.fanout", 4.0, {})])
    spans += _turn(3, 0.40, 58.5, [("decode.step", 58.0, {"batch": 2})])
    # the children of the runtime's spans must not be read as steps
    spans += [("decode.step.fetch", 0.0, 0.050, {"parent_id": 10010}),
              # request lanes
              ("decode.queue_wait", 0.0, 0.020, {"trace_id": 7}),
              ("decode.queue_wait", 0.1, 0.040, {"trace_id": 8}),
              ("decode.ride_prefill", 0.1, 0.130, {"trace_id": 8}),
              ("gateway.first_frame", 0.2, 0.001, {"trace_id": 7}),
              ("gateway.first_frame", 0.3, 0.004, {"trace_id": 8})]
    return {"spans": spans, "cell": Cell(CELL)}


@pytest.mark.parametrize("name,want", [
    ("step_span_p50_ms", 58.0),                 # median of 57, 59, 58
    ("loop_host_p50_ms", 3.0),                  # 60-57, 200-189, 58.5-58
    ("sched_queue_wait_mean_ms", 30.0),
    ("first_frame_egress_mean_ms", 2.5)])
def test_span_readers_on_hand_built_spans(name, want):
    assert readers.read_metric(name, _span_obs()) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_in_a_program_without_the_spans(name):
    """The parent's program: its ``decode.step`` span exists (and times the
    dispatch), nothing else does; no reader raises, and only the metric
    whose span the parent has gives a number."""
    obs = {"spans": [("decode.step", 0.0, 0.001, {"batch": 1}),
                     ("decode.prefill", 0.1, 0.125, {"batch": 1, "seq": 64}),
                     ("decode.prefill", 0.1, 0.126, {"seq_bucket": 64})],
           "trace": None, "cell": Cell(CELL)}
    got = readers.read_metric(name, obs)
    assert (got == pytest.approx(1.0)) if name == "step_span_p50_ms" \
        else got is None


def test_self_segments_cut_spans_to_what_no_child_covers():
    spans = [("decode.boundary", 0.0, 10.0), ("decode.admit", 0.0, 1.0),
             ("decode.step", 2.0, 9.0), ("decode.step.dispatch", 2.0, 3.0),
             # starts where its sibling ends, a rounding early
             ("decode.step.fetch", 3.0 - 1e-9, 9.0),
             ("decode.idle", 10.0, 12.0)]
    got = idle_phases.self_segments(spans)
    assert [(n, round(s, 6), round(e, 6)) for n, s, e in got] == [
        ("decode.admit", 0.0, 1.0), ("decode.boundary", 1.0, 2.0),
        ("decode.step.dispatch", 2.0, 3.0), ("decode.step.fetch", 3.0, 9.0),
        ("decode.boundary", 9.0, 10.0), ("decode.idle", 10.0, 12.0)]


def _reduced():
    """Device busy 1-2 and 3-4 of a window 0-5; the scheduler's thread idle
    to 0.9, one turn 0.9-2.6, a second 2.7-4.7 with no children."""
    dev = {"/device:TPU:0": {"merged": [[1.0, 2.0], [3.0, 4.0]],
                             "busy_s": 2.0}}
    host = [("python", "decode.idle", 0.0, 0.9),
            ("python", "decode.boundary", 0.9, 1.7),
            ("python", "decode.admit", 0.9, 0.05),
            ("python", "decode.step", 0.95, 1.15),
            ("python", "decode.step.dispatch", 0.95, 0.1),
            ("python", "decode.step.fetch", 1.05, 1.05),
            ("python", "decode.step.fanout", 2.1, 0.5),
            ("python", "decode.boundary", 2.7, 2.0)]
    return trace_reduce.Reduced(dev, host, 0.0, 5.0)


def test_idle_by_phase_on_hand_built_intervals():
    got = idle_phases.by_phase(_reduced())
    assert got["window_s"] == 5.0 and got["idle_s"] == pytest.approx(3.0)
    assert got["clock_skew_s"] == 0.0     # every start of work is in a span
    assert got["phases"] == pytest.approx({
        "decode.idle": 0.9, "decode.admit": 0.05,
        "decode.step.dispatch": 0.05,       # until the device starts at 1.0
        "decode.step.fetch": 0.1,           # device done at 2.0, woken at 2.1
        "decode.step.fanout": 0.5, "decode.boundary": 1.0,
        idle_phases.OUTSIDE: 0.4})
    assert sum(got["phases"].values()) == pytest.approx(got["idle_s"])
    # no scheduler loop in the trace, or no device: nothing to say
    r = _reduced()
    r.host = [h for h in r.host if h[1] != "decode.boundary"]
    assert idle_phases.by_phase(r) is None
    assert idle_phases.by_phase(
        trace_reduce.Reduced({}, _reduced().host, 0.0, 5.0)) is None


def test_clock_skew_is_the_least_shift_causality_asks_for():
    """Device work that starts before the span that launched it: the
    device's line is ahead by at least the widest such lead.  Work that
    starts inside a ``.dispatch`` or ``.fetch`` span asks for nothing, nor
    does work with no such span within 5 ms after it."""
    segs = [("decode.step.prepare", 0.0, 1.0),
            ("decode.step.dispatch", 1.0, 1.1),
            ("decode.step.fetch", 1.1, 2.0),
            ("decode.step.fanout", 2.0, 2.1),
            ("decode.step.dispatch", 2.1, 2.2),
            ("decode.step.fetch", 2.2, 3.0)]
    assert idle_phases.clock_skew([[1.05, 1.9], [2.15, 2.9]], segs) == 0.0
    assert idle_phases.clock_skew([[0.9990, 1.9], [2.0988, 2.9]], segs) == \
        pytest.approx(0.0012)
    assert idle_phases.clock_skew([[0.5, 0.6], [2.0988, 2.9]], segs) == \
        pytest.approx(0.0012)
    # by_phase moves the device's line by it before cutting the idle time
    r = _reduced()
    dev = r.devices["/device:TPU:0"]
    dev["merged"] = [[a - 0.002, b - 0.002] for a, b in dev["merged"]]
    got = idle_phases.by_phase(r)
    # 0.998 lies in the dispatch span already; 2.998 has no span to wait for
    assert got["clock_skew_s"] == 0.0
    dev["merged"] = [[0.947, 1.947], [2.947, 3.947]]   # 0.947: in the admit
    got = idle_phases.by_phase(r)
    assert got["clock_skew_s"] == pytest.approx(0.003)
    assert got["phases"].get("decode.step.dispatch", 0.0) == pytest.approx(0.0)
    assert sum(got["phases"].values()) == pytest.approx(got["idle_s"])


def test_idle_by_phase_on_the_recorded_tpu_trace():
    """``perf/testdata/toy_phases_v5e.xplane.pb`` (a v5e, Python tracer off,
    ``perf/tools/phase_probe.py``): a thread turns four times through
    admit 2 ms, prepare 3 ms, one 5.8 ms program (dispatch, fetch), fan-out
    1 ms, then idles 10 ms; the window runs from the first to the last
    device event, so it holds three idles and three whole turns' host
    phases."""
    assert os.path.getsize(TOY) < 100_000
    r = trace_reduce.reduce_trace(TOY, host_prefixes=("decode.",))
    assert list(r.devices) == ["/device:TPU:0"]
    assert {name for _th, name, _s, _d in r.host} == {
        "decode.boundary", "decode.admit", "decode.step.prepare",
        "decode.step", "decode.step.dispatch", "decode.step.fetch",
        "decode.step.fanout", "decode.idle"}
    n, total = r.module_seconds(r"^jit_step\(")
    assert n == 4 and total == pytest.approx(4 * 5.824e-3, rel=1e-3)
    got = idle_phases.by_phase(r)
    # on the device's line each program starts 0.3 to 1.2 ms before its
    # dispatch span opens on the host's
    assert got["clock_skew_s"] == pytest.approx(1.1624e-3, rel=1e-3)
    assert got["window_s"] == pytest.approx(r.window_s)
    assert got["idle_s"] == pytest.approx(r.window_s - r.busy_s)
    ph = got["phases"]
    assert sum(ph.values()) == pytest.approx(got["idle_s"])
    assert 3 * 0.010 < ph["decode.idle"] < 3 * 0.0115
    assert 3 * 0.002 < ph["decode.admit"] < 3 * 0.0025
    assert 3 * 0.003 < ph["decode.step.prepare"] < 3 * 0.0036
    assert 3 * 0.001 < ph["decode.step.fanout"] < 4 * 0.002
    # the thread waits for the chip: what idles in a fetch is the hand-over
    # after the program's end, about 1.2 ms a turn once the skew is out
    assert 3 * 0.0008 < ph["decode.step.fetch"] < 3 * 0.0016
    assert ph["decode.step.dispatch"] < 0.001
    assert ph[idle_phases.OUTSIDE] < 1e-4 and ph["decode.boundary"] < 1e-4
    loop = sum(v for k, v in ph.items() if k not in (
        "decode.idle", "decode.step.fetch", idle_phases.OUTSIDE))
    assert 100 * loop / got["window_s"] == pytest.approx(26.49, abs=0.05)


def test_idle_readers_share_one_reduction_and_print_it_once(
        monkeypatch, capsys):
    """Both idle metrics on one hand-built observation: the run's trace is
    reduced again with the ``decode.`` prefix (``obs["trace"].host`` may be
    cut), once, and the people's line comes before any result."""
    calls = []

    def reduce_again(path, host_prefixes=None, keep_host=20000):
        calls.append((path, host_prefixes))
        return _reduced()

    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: d + "/x.pb")
    monkeypatch.setattr(trace_reduce, "reduce_trace", reduce_again)
    obs = {"trace": object(), "cell": types.SimpleNamespace(
        root="/checkout", metric_file=Cell(CELL).metric_file,
        bench_dir=Cell(CELL).bench_dir)}
    loop = readers.read_metric("idle_host_loop_share", obs)
    none = readers.read_metric("idle_no_work_share", obs)
    # inside a boundary and outside a fetch: 0.05 + 0.05 + 0.5 + 1.0 of 5 s
    assert loop == pytest.approx(100 * 1.6 / 5.0)
    assert none == pytest.approx(100 * 0.9 / 5.0)
    assert calls == [("/checkout/.perf_out/trace/x.pb", ("decode.",))]
    out = capsys.readouterr().out.splitlines()
    printed = [ln for ln in out if ln.startswith("idle_by_phase ")]
    assert len(printed) == 1
    assert sum(ln.startswith("device_clock_ahead_ms 0.000") for ln in out) == 1
    assert json.loads(printed[0][len("idle_by_phase "):])[
        "decode.boundary"] == pytest.approx(1.0)
    # the named shares and the rest add up to the device's idle share
    fetch_and_outside = 100 * (0.1 + 0.4) / 5.0
    assert loop + none + fetch_and_outside == pytest.approx(
        100 * (1 - 2.0 / 5.0))


def test_new_metrics_are_entries_appended_for_the_serving_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    assert tuple(m["name"] for m in per_layer[-len(NEW):]) == NEW
    for m in per_layer[-len(NEW):]:
        assert m["workloads"] == [CELL]
        assert m["source"] == ("device_trace" if m["name"].startswith("idle_")
                               else "program_span")
    train = [m["name"] for m in Cell("bert_base.pretrain_s512").per_layer()]
    assert not set(train) & set(NEW)


def test_traced_rehearsal_reads_the_span_metrics_on_the_cpu(
        tiny_root):  # noqa: F811
    """The tiny server, traced on the CPU: the four span metrics have
    values, every older metric is still there, and the two that need a
    device plane are left out (not zero, not an error)."""
    _cell, out, line = _run(tiny_root, CELL, _args(seed=2**31 + 77, trace=1))
    got = line["metrics"]
    assert set(NEW[:4]) <= set(got) and not set(NEW[4:]) & set(got)
    assert {"prefill_p50_ms", "decode_step_p50_ms",
            "gateway_queue_wait_p50_ms", "rows_per_step"} <= set(got)
    step = got["step_span_p50_ms"]["value"]
    assert 0 < got["loop_host_p50_ms"]["value"] < step
    assert got["sched_queue_wait_mean_ms"]["value"] > \
        got["gateway_queue_wait_p50_ms"]["value"]
    assert 0 <= got["first_frame_egress_mean_ms"]["value"] < 1000
    # every finished request left one queue wait and one first frame
    names = [s[0] for s in out["obs"]["spans"]]
    assert names.count("decode.queue_wait") == line["attempted"] \
        == names.count("gateway.first_frame")
