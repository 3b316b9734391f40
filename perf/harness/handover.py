"""The hand-over between the scheduler's thread and the device, step by step.

In the decode loop one program is in flight at a time: launched inside a
``.dispatch`` span of ``runtime.py`` and awaited by the ``.fetch`` span behind
it.  A call (``decode.step``, ``decode.prefill``, ``decode.verify``) is taken
from its ``.dispatch``'s start ``S`` to its ``.fetch``'s end ``E`` on the
host's line, and its work on the first device's line from its first
operation's start ``d0`` to its last one's end ``d1`` (a prefill's two
programs, block and commit, are one extent).  Then

- ``handover = (E - S) - (d1 - d0)``: the span minus its program's extent on
  the device.  Both differences are taken on ONE clock each, so the number
  is exact whatever the two clocks' disagreement;
- the shift ``delta`` by which the device's line runs ahead of the host's
  obeys ``delta >= S - d0`` for every call (a program cannot start before it
  was launched: ``lo``, what ``idle_phases.clock_skew`` looks for) and
  ``delta <= E - d1`` for every call (the thread cannot have its tokens
  before the program ended: ``hi``).  ``lo > hi`` means the programs were
  given to the wrong spans, and there is no result;
- ``launch = d0 + delta - S`` and ``wake = E - d1 - delta`` at the bracket's
  middle: ``launch + wake = handover`` call by call, and each is known to
  half the bracket's width.

The device's busy intervals are first joined into programs (work that follows
on within 20 us); a program is given to the call whose span it overlaps most,
first with no shift and then again at the bracket's middle until nothing
moves, and one that overlaps no call's span is nobody's (a page copy that a
fan-out launched, a copy-on-write before a step).  The first and the last
call of the traced window take their programs and are then left out: either
may be cut on one line and not the other.

Host-clock readers (``outside_profiler``): the bus is on for the whole window
and the profiler's Python tracer for ``trace_window_s`` of it, so a span that
starts well outside those seconds describes the loop without the tracer.
"""
import json
import os

from . import stats, trace_reduce
from .idle_phases import LAUNCH_SUFFIX, LOOP, PREFIX, WAIT_SUFFIX

STEP = "decode.step"
# device work nearer than this to the work before it is the same program's
_JOIN = 20e-6
# spans nearer than this to either edge of the profiler's seconds count on
# neither side: what the window's opening is off by, and the tracer's start
_EDGE = 1.5


def calls(reduced):
    """``[(kind, S, E)]`` of the scheduler's thread, ascending: every
    runtime call that has both its ``.dispatch`` and its ``.fetch`` in the
    trace."""
    threads = {th for th, name, _s, _d in reduced.host if name == LOOP}
    out, launched = [], None
    for _th, name, s, d in sorted(
            (h for h in reduced.host if h[0] in threads), key=lambda h: h[2]):
        if name.endswith(LAUNCH_SUFFIX):
            launched = (name[:-len(LAUNCH_SUFFIX)], s)
        elif name.endswith(WAIT_SUFFIX) and launched is not None:
            if launched[0] == name[:-len(WAIT_SUFFIX)]:
                out.append((launched[0], launched[1], s + d))
            launched = None
    return out


def programs(busy):
    """The device's busy intervals (merged, ascending) joined into extents
    of work that follows on within ``_JOIN``: a program's operations follow
    each other within microseconds, two programs of one call within a few
    tens, and work that another phase of the loop launched (a page copy
    behind a prefill, a copy-on-write before a step) stands apart."""
    out = []
    for a, b in busy:
        if out and a - out[-1][1] < _JOIN:
            out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _own(found, extents, shift):
    """Per call the ``(d0, d1)`` of the extents that, with the device's line
    moved later by ``shift``, overlap its span more than any other call's
    (``None`` where none does).  Work that overlaps no call is nobody's."""
    out, k = [None] * len(found), 0
    for a, b in extents:
        a, b = a + shift, b + shift
        while k < len(found) - 1 and found[k][2] <= a:
            k += 1
        best, most, j = None, 0.0, k
        while j < len(found) and found[j][1] < b:
            cover = min(b, found[j][2]) - max(a, found[j][1])
            if cover > most:
                best, most = j, cover
            j += 1
        if best is not None:
            d0, d1 = out[best] or (a - shift, b - shift)
            out[best] = (min(d0, a - shift), max(d1, b - shift))
    return out


def bracket(found, busy):
    """``(lo, hi, rows)``: the bracket of the device line's lead in seconds
    and per matched call ``(kind, S, E, d0, d1)``, the window's first and
    last call left out; ``None`` where no call has device work or the
    bracket is empty (``lo > hi``)."""
    extents = programs(busy)
    shift, own = 0.0, None
    for _round in range(4):
        again = _own(found, extents, shift)
        if again == own:
            break
        own = again
        rows = [call + d for call, d in zip(found[1:-1], own[1:-1])
                if d is not None]
        if not rows:
            return None
        lo = max(s - d0 for _k, s, _e, d0, _d1 in rows)
        hi = min(e - d1 for _k, _s, e, _d0, d1 in rows)
        shift = (lo + hi) / 2
    return (lo, hi, rows) if lo <= hi else None


def by_call(reduced):
    """``{"lo_s", "hi_s", "calls": [{"kind", "span_s", "device_s",
    "handover_s", "launch_s", "wake_s"}]}`` of a :class:`trace_reduce.Reduced`
    whose host events are the ``decode.`` spans, or ``None``."""
    if not reduced.devices:
        return None
    got = bracket(calls(reduced),
                  next(iter(reduced.devices.values()))["merged"])
    if got is None:
        return None
    lo, hi, rows = got
    delta = (lo + hi) / 2
    return {"lo_s": lo, "hi_s": hi, "calls": [
        {"kind": kind, "span_s": e - s, "device_s": d1 - d0,
         "handover_s": (e - s) - (d1 - d0),
         "launch_s": d0 + delta - s, "wake_s": e - d1 - delta}
        for kind, s, e, d0, d1 in rows]}


def _p50_ms(rows, key):
    return 1e3 * stats.median([r[key] for r in rows])


def handover(obs):
    """:func:`by_call` of the run's own trace, read once per run (kept in
    ``obs``) and printed once: the bracket, and per kind of call the medians
    of span = device + launch + wake."""
    if "handover" not in obs:
        found = None
        if obs.get("trace") is not None:
            root = os.path.join(obs["cell"].root, ".perf_out", "trace")
            found = by_call(trace_reduce.reduce_trace(
                trace_reduce.find_xplane(root), host_prefixes=(PREFIX,)))
        if found is not None:
            lo, hi = found["lo_s"] * 1e3, found["hi_s"] * 1e3
            print(f"device_clock_ahead_ms_bracket [{lo:.4f}, {hi:.4f}] "
                  f"(launch and wake at its middle, each known to "
                  f"{(hi - lo) / 2:.4f} ms)", flush=True)
            kinds = {}
            for row in found["calls"]:
                kinds.setdefault(row["kind"], []).append(row)
            print("handover_by_kind " + json.dumps({
                kind: dict(n=len(rows), **{
                    key[:-2] + "_p50_ms": round(_p50_ms(rows, key), 4)
                    for key in ("span_s", "device_s", "handover_s",
                                "launch_s", "wake_s")})
                for kind, rows in sorted(kinds.items())}), flush=True)
        obs["handover"] = found
    return obs["handover"]


def step_p50_ms(obs, key):
    """Median over the traced ``decode.step`` calls of ``key`` (one of
    ``handover_s``, ``launch_s``, ``wake_s``), in ms."""
    found = handover(obs)
    rows = [r for r in (found or {}).get("calls", ()) if r["kind"] == STEP]
    return _p50_ms(rows, key) if rows else None


# ------------------------------------------------- host clock, tracer aside
def profiler_seconds(obs):
    """``(t0, t1)`` on the bus's clock of the seconds the profiler ran, as
    the driver places them: the window opens at the first span's start
    (``tel.reset()`` runs just before it); ``None`` where the cell has no
    ``trace_window_s``.  The driver places the start by ``--seconds``, this
    by ``window_wall_s``, which has the drain in it: the two agree while
    ``--seconds`` is at least twice the start plus the length (35 s for
    ``[15, 5]``; the benchmark runs 50), and below that differ by half the
    drain, which ``_EDGE`` covers up to a drain of 3 s."""
    spans = obs.get("spans") or []
    window = obs["cell"].traffic.get("trace_window_s")
    wall = (obs.get("values") or {}).get("window_wall_s")
    if not spans or not window or wall is None:
        return None
    start, length = window
    t0 = min(s for _n, s, _d, _a in spans) \
        + min(start, max(wall - length, 0) / 2)
    return t0, t0 + length


def outside_profiler(obs, name, inside=False):
    """The bus spans ``(dur_s, attrs)`` called ``name`` that start more than
    ``_EDGE`` outside the profiler's seconds (``inside``: more than that
    inside them)."""
    edges = profiler_seconds(obs)
    if edges is None:
        return []
    t0, t1 = edges
    return [(d, attrs or {}) for n, s, d, attrs in obs["spans"]
            if n == name and (t0 + _EDGE < s < t1 - _EDGE if inside
                              else s < t0 - _EDGE or s > t1 + _EDGE)]


def cpu_spans(obs, name):
    """``[(dur_ms, cpu_ms, attrs)]`` of the spans called ``name`` outside
    the profiler's seconds that carry ``cpu_ms`` (a program from before the
    spans carried it gives none)."""
    return [(d * 1e3, attrs["cpu_ms"], attrs)
            for d, attrs in outside_profiler(obs, name) if "cpu_ms" in attrs]


def _first_time(obs, name):
    """True once per run and ``name``: the lines for people print once."""
    seen = obs.setdefault("handover_printed", set())
    if name in seen:
        return False
    seen.add(name)
    return True


def cpu_mean_ms(obs, name, off_cpu=False):
    """Mean ``cpu_ms`` of the spans called ``name`` (``off_cpu``: mean of
    ``dur - cpu_ms``, the time the thread stood off the CPU inside them: in
    the runtime's own blocking or runnable without the interpreter's lock;
    the two cannot be parted from here, and a cell whose interpreter is free
    reads the first alone).

    Means, because a host's CPU clocks may advance in ticks of milliseconds
    (the chip's host: one span's ``cpu_ms`` reads 0 or a tick of 10 ms), so
    a median of ``cpu_ms`` says nothing there; a sum over many spans is
    unbiased, to about one part in the root of the ticks it holds.  Once per
    run the line ``span_by_clock <name>`` has both means with the spans'
    mean and median duration, the ticks in the sum, and the least ``cpu_ms``
    above zero that any span read (the clock's grain, or less)."""
    rows = cpu_spans(obs, name)
    if not rows:
        return None
    dur = sum(d for d, _c, _a in rows)
    cpu = sum(c for _d, c, _a in rows)
    if _first_time(obs, name):
        least = min((c for _d, c, _a in rows if c > 0), default=0.0)
        print(f"span_by_clock {name} " + json.dumps({
            "n": len(rows),
            "dur_p50_ms": round(stats.median([d for d, _c, _a in rows]), 4),
            "dur_mean_ms": round(dur / len(rows), 4),
            "cpu_mean_ms": round(cpu / len(rows), 4),
            "off_cpu_mean_ms": round((dur - cpu) / len(rows), 4),
            "cpu_share": round(cpu / dur, 4),
            "least_cpu_ms": round(least, 4),
            "ticks": round(cpu / least) if least else 0}), flush=True)
    return (dur - cpu if off_cpu else cpu) / len(rows)


def loop_cpu(obs, name):
    """Over the turns (spans ``name``, ``decode.boundary``) outside the
    profiler's seconds: ``{"share_pct"`` (the thread's CPU seconds over the
    turns' seconds), ``"other_cores"`` (every other thread's CPU seconds over
    the same seconds)``}``; printed once, with the door's part as
    ``gateway.handler_cpu_ms`` has it (over the WHOLE window: a counter has
    no seconds of its own).  ``None`` where the turns carry no
    ``proc_cpu_ms``."""
    rows = [(d, c, a["proc_cpu_ms"]) for d, c, a in cpu_spans(obs, name)
            if "proc_cpu_ms" in a]
    if not rows:
        return None
    wall = sum(d for d, _c, _p in rows)
    out = {"share_pct": 100.0 * sum(c for _d, c, _p in rows) / wall,
           "other_cores": sum(p - c for _d, c, p in rows) / wall}
    if _first_time(obs, name):
        line = {"turns": len(rows),
                "scheduler": round(out["share_pct"] / 100, 4),
                "others": round(out["other_cores"], 4)}
        door = (obs.get("counters") or {}).get("gateway.handler_cpu_ms")
        seconds = (obs.get("values") or {}).get("window_wall_s")
        if door is not None and seconds:
            line["door"] = round(door / 1e3 / seconds, 4)
            line["rest"] = round(line["others"] - line["door"], 4)
        print("cpu_cores " + json.dumps(line), flush=True)
    return out


def profiler_stretch(obs, name):
    """Median duration of the spans ``name`` that start inside the
    profiler's seconds over the median of those outside, less 1, in percent:
    what the profiler adds to every host number of a traced run."""
    inside = [d for d, _a in outside_profiler(obs, name, inside=True)]
    outside = [d for d, _a in outside_profiler(obs, name)]
    if not inside or not outside:
        return None
    return 100.0 * (stats.median(inside) / stats.median(outside) - 1.0)
