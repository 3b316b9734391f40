"""Device idle time by the phase the scheduler's thread was in.

The program's own spans are ``jax.profiler.TraceAnnotation``s in the
``/host:CPU`` plane of the ``.xplane.pb``, on the device trace's time axis.
The scheduler's thread is the line that holds ``decode.boundary`` events.  (A
line's name is not the thread's: every Python thread's line is called
``python``.  Inside the window only the scheduler's thread opens ``decode.``
spans, so the prefix is what tells it apart.)  Each instant in which the
first device runs nothing is given to the innermost ``decode.`` span of that
thread that covers it, or to ``outside`` where none does:

- inside ``decode.boundary`` but outside a ``.fetch`` span, the host had not
  yet fed the chip (admission, preparing arrays, dispatch, token fan-out);
- inside a ``.fetch`` span the thread was itself waiting for the chip, so
  what idles there is the hand-over, not the loop;
- inside ``decode.idle`` there was no work.

The two clocks of a trace do not agree to better than a millisecond or two:
in the recorded toy trace every program starts on the device's line 0.7 to
1.2 ms BEFORE the ``.dispatch`` span that launched it opens on the host's.
In this loop the device can only run something while the thread is inside a
``.dispatch`` or a ``.fetch`` span (every program is awaited by a fetch), so
the device's line is first moved later by the least shift that puts every
start of device work at or after the start of the span that can have
launched it (:func:`clock_skew`; a lower bound, the launch itself takes
time).  Without it the skew times the number of turns is billed to the
``.fetch`` spans and taken from the host's phases.

``obs["trace"].host`` is not the source: the reducer keeps the first 20,000
host events in line order, and a window under the profiler's Python tracer
holds far more.  The trace is reduced again with ``host_prefixes``, which
filters before that cap.  A trace with no such spans (a program from before
they existed, a CPU run) gives ``None``.
"""
import json
import os
from bisect import bisect_right

from . import trace_reduce

PREFIX = "decode."
LOOP = "decode.boundary"
NO_WORK = "decode.idle"
WAIT_SUFFIX = ".fetch"
LAUNCH_SUFFIX = ".dispatch"
OUTSIDE = "outside"
# a start of device work further than this before the next span that could
# have launched it is someone else's work, not skew
_MAX_SKEW = 5e-3
# the reducer's seconds are floats made from whole nanoseconds: a span that
# ends where its sibling starts may read a rounding later
_EPS = 1e-6


def self_segments(spans):
    """``[(name, start, end)]`` of one thread, nested -> the same list cut
    to self time: the parts of each span that no child covers."""
    out, stack = [], []         # stack rows: [name, end, covered_up_to]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, cur = stack.pop()
            if cur < end:
                out.append((name, cur, end))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        close(s + _EPS)
        if stack:
            top = stack[-1]
            if top[2] < s:
                out.append((top[0], top[2], s))
            top[2] = max(top[2], s)
            e = min(e, top[1])      # a child never outlasts its parent
        stack.append([name, e, s])
    close(float("inf"))
    return out


def _overlap(segments, gaps):
    """Seconds of each named segment that fall inside ``gaps`` (merged,
    ascending): ``{name: seconds}``."""
    out, j = {}, 0
    for name, s, e in sorted(segments, key=lambda x: x[1]):
        while j < len(gaps) and gaps[j][1] <= s:
            j += 1
        k = j
        while k < len(gaps) and gaps[k][0] < e:
            cov = min(e, gaps[k][1]) - max(s, gaps[k][0])
            if cov > 0:
                out[name] = out.get(name, 0.0) + cov
            k += 1
    return out


def clock_skew(busy, segments):
    """Seconds by which the device's line runs ahead of the host's, at
    least: the least shift after which every busy interval of ``busy``
    (merged, ascending) starts inside a ``.dispatch`` or ``.fetch`` self
    segment.  An interval further than ``_MAX_SKEW`` before the next such
    segment is not counted."""
    may_run = sorted((s, e) for name, s, e in segments
                     if name.endswith((LAUNCH_SUFFIX, WAIT_SUFFIX)))
    starts = [s for s, _e in may_run]
    skew = 0.0
    for b0, _b1 in busy:
        i = bisect_right(starts, b0)
        if i and b0 < may_run[i - 1][1]:
            continue
        if i < len(may_run) and may_run[i][0] - b0 <= _MAX_SKEW:
            skew = max(skew, may_run[i][0] - b0)
    return skew


def by_phase(reduced):
    """``{"window_s", "idle_s", "clock_skew_s", "phases": {name: idle
    seconds}}`` of a :class:`trace_reduce.Reduced` whose host events are the
    ``decode.`` spans, or ``None`` where it holds no device or no scheduler
    loop."""
    if not reduced.devices or not reduced.window_s:
        return None
    threads = {th for th, name, _s, _d in reduced.host if name == LOOP}
    if not threads:
        return None
    segments = self_segments(
        [(name, s, s + d) for th, name, s, d in reduced.host
         if th in threads and name.startswith(PREFIX)])
    busy = next(iter(reduced.devices.values()))["merged"]
    skew = clock_skew(busy, segments)
    edges = [reduced.t_first] + [t for iv in busy for t in iv] \
        + [reduced.t_last]
    gaps = [(a + skew, b + skew)
            for a, b in zip(edges[::2], edges[1::2]) if b > a]
    idle = sum(b - a for a, b in gaps)
    phases = _overlap(segments, gaps)
    phases[OUTSIDE] = max(idle - sum(phases.values()), 0.0)
    return {"window_s": reduced.window_s, "idle_s": idle,
            "clock_skew_s": skew, "phases": phases}


def phases(obs):
    """:func:`by_phase` of the run's own trace, read once per run (kept in
    ``obs``) and printed once as ``idle_by_phase {phase: seconds}``."""
    if "idle_by_phase" not in obs:
        found = None
        if obs.get("trace") is not None:
            root = os.path.join(obs["cell"].root, ".perf_out", "trace")
            found = by_phase(trace_reduce.reduce_trace(
                trace_reduce.find_xplane(root), host_prefixes=(PREFIX,)))
        if found is not None:
            print(f"device_clock_ahead_ms {found['clock_skew_s'] * 1e3:.3f}"
                  " (least shift that puts every start of device work "
                  "inside the span that launched it)", flush=True)
            print("idle_by_phase " + json.dumps(
                {k: round(v, 6) for k, v in sorted(
                    found["phases"].items(), key=lambda kv: -kv[1])}),
                flush=True)
        obs["idle_by_phase"] = found
    return obs["idle_by_phase"]


def host_loop_share(obs):
    """Percent of the traced window in which the first device ran nothing
    while the scheduler's thread was inside ``decode.boundary`` and outside
    every ``.fetch`` span."""
    found = phases(obs)
    if found is None:
        return None
    loop = sum(v for k, v in found["phases"].items()
               if k not in (NO_WORK, OUTSIDE) and not k.endswith(WAIT_SUFFIX))
    return 100.0 * loop / found["window_s"]


def no_work_share(obs):
    """The same, inside ``decode.idle``."""
    found = phases(obs)
    if found is None:
        return None
    return 100.0 * found["phases"].get(NO_WORK, 0.0) / found["window_s"]


def span_mean_ms(obs, name):
    """Mean duration in ms of the bus spans called ``name`` (kept here so
    that the metrics this file serves share one helper)."""
    durs = [d for n, _s, d, _a in obs.get("spans") or [] if n == name]
    return 1e3 * sum(durs) / len(durs) if durs else None
