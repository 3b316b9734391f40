"""From a profiler trace (``.xplane.pb``) to numbers: device busy and idle
time, time per operation, collective time not hidden behind compute, and the
longest idle gaps named by what the host was doing.

Layout, as a TPU v5e's trace has it (``perf/tools/trace_probe.py`` prints
it): one plane ``/device:TPU:<n>`` per chip with the lines ``XLA Modules``
(one event per executed program, named ``jit_<fn>(<hash>)``), ``XLA Ops``
(one event per HLO instruction, named by its whole HLO text) and ``Async XLA
Ops``; one plane ``/host:CPU`` with a line per thread, where
``jax.profiler.TraceAnnotation`` spans sit beside runtime calls.  Device and
host events share one time axis to within about a millisecond.
"""
import glob
import os
import re

_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")
_SUFFIX = re.compile(r"\.\d+$")


def op_kind(hlo_text):
    """Short name of an ``XLA Ops`` event: the instruction's name without
    ``%`` and without its numeric suffix (``%fusion.12 = ...`` -> ``fusion``,
    ``%multiply_reduce_fusion.3`` -> ``multiply_reduce_fusion``)."""
    name = hlo_text.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", name)


def _union(intervals):
    """Total length and merged list of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _subtract(intervals, cover):
    """Total length of ``intervals`` (merged) not covered by ``cover``
    (merged)."""
    total, j = 0.0, 0
    for s, e in intervals:
        cur = s
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            if cover[k][0] > cur:
                total += cover[k][0] - cur
            cur = max(cur, cover[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


class Reduced:
    """What one trace says.  Seconds throughout.

    ``devices``: per device plane ``{"busy_s", "ops": {kind: s},
    "op_events": [(hlo_text, start_s, dur_s)], "modules": {name: [dur_s]},
    "collective_s", "collective_exposed_s", "merged": [[s, e]]}``.
    ``host``: ``[(thread, name, start_s, dur_s)]`` of the host plane.
    ``window_s``: the traced window (first to last event of any plane that
    ran something)."""

    def __init__(self, devices, host, t_first, t_last):
        self.devices, self.host = devices, host
        self.t_first, self.t_last = t_first, t_last
        self.window_s = max(t_last - t_first, 0.0)

    @property
    def busy_s(self):
        """Seconds an operation ran, averaged over the device planes."""
        if not self.devices:
            return 0.0
        return sum(d["busy_s"] for d in self.devices.values()) / len(
            self.devices)

    def op_seconds(self):
        """``{kind: seconds}`` averaged over the devices."""
        out = {}
        for d in self.devices.values():
            for k, v in d["ops"].items():
                out[k] = out.get(k, 0.0) + v / len(self.devices)
        return out

    def matching_seconds(self, patterns):
        """Seconds of ``XLA Ops`` events whose HLO text matches any of the
        regular expressions, averaged over the devices."""
        regs = [re.compile(p) for p in patterns]
        total = 0.0
        for d in self.devices.values():
            total += sum(dur for text, _s, dur in d["op_events"]
                         if any(r.search(text) for r in regs))
        return total / max(len(self.devices), 1)

    def module_seconds(self, pattern):
        """``(executions, total seconds)`` of the programs whose name
        matches, averaged over the devices."""
        reg = re.compile(pattern)
        n = total = 0.0
        for d in self.devices.values():
            for name, durs in d["modules"].items():
                if reg.search(name):
                    n += len(durs)
                    total += sum(durs)
        k = max(len(self.devices), 1)
        return n / k, total / k

    def idle_gaps(self, top=10, annotations=None):
        """The longest gaps in which no operation ran on the first device,
        each named by the host span that covers most of it:
        ``[(name, seconds)]``.  ``annotations`` restricts the naming to
        host events whose name starts with one of the prefixes; a gap that
        none covers is ``host:unattributed``."""
        if not self.devices:
            return []
        merged = next(iter(self.devices.values()))["merged"]
        gaps = [(merged[i + 1][0] - merged[i][1], merged[i][1],
                 merged[i + 1][0]) for i in range(len(merged) - 1)]
        gaps.sort(reverse=True)
        spans = [(s, s + d, name) for _th, name, s, d in self.host
                 if annotations is None
                 or any(name.startswith(p) for p in annotations)]
        out = []
        for length, g0, g1 in gaps[:top]:
            best, best_cov = "host:unattributed", 0.0
            for s, e, name in spans:
                cov = min(e, g1) - max(s, g0)
                # a later span that covers as much is nested in the earlier
                # one and says more
                if cov > 0 and cov >= best_cov - 1e-9:
                    best, best_cov = "host:" + name, cov
            out.append((best, length))
        return out


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_trace(path, host_prefixes=None, keep_host=20000):
    """Read one ``.xplane.pb`` into a :class:`Reduced`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    t_first, t_last = float("inf"), 0.0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") or \
                plane.name.startswith("/device:GPU:"):
            ops, op_events, modules, intervals = {}, [], {}, []
            coll, compute = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        s, d = ev.start_ns / 1e9, ev.duration_ns / 1e9
                        kind = op_kind(ev.name)
                        ops[kind] = ops.get(kind, 0.0) + d
                        op_events.append((ev.name, s, d))
                        intervals.append((s, s + d))
                        (coll if _COLLECTIVE.match(kind) else compute
                         ).append((s, s + d))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        modules.setdefault(ev.name, []).append(
                            ev.duration_ns / 1e9)
                        s = ev.start_ns / 1e9
                        t_first = min(t_first, s)
                        t_last = max(t_last, s + ev.duration_ns / 1e9)
                elif line.name == "Async XLA Ops":
                    # a collective in flight: start to done
                    for ev in line.events:
                        if _COLLECTIVE.match(op_kind(ev.name)):
                            s = ev.start_ns / 1e9
                            coll.append((s, s + ev.duration_ns / 1e9))
            busy, merged = _union(intervals)
            coll_s, coll_merged = _union(coll)
            _c, compute_merged = _union(compute)
            devices[plane.name] = {
                "busy_s": busy, "ops": ops, "op_events": op_events,
                "modules": modules, "merged": merged,
                "collective_s": coll_s,
                "collective_exposed_s": _subtract(coll_merged,
                                                  compute_merged)}
            if intervals:
                t_first = min(t_first, merged[0][0])
                t_last = max(t_last, merged[-1][1])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if host_prefixes is not None and not any(
                            name.startswith(p) for p in host_prefixes):
                        continue
                    if len(host) < keep_host:
                        host.append((line.name, name, ev.start_ns / 1e9,
                                     ev.duration_ns / 1e9))
    if t_first == float("inf"):
        t_first = t_last = 0.0
    return Reduced(devices, host, t_first, t_last)


class Tracer:
    """Starts and stops the profiler around a part of the window and hands
    back the reduction.  Training: ``at_step``; serving: ``start`` /
    ``stop`` from a timer."""

    def __init__(self, out_dir):
        self.dir = out_dir
        self.on = False
        self.done = False
        self.first = None

    def start(self):
        import jax
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self.on = True

    def stop(self):
        if self.on:
            import jax
            jax.profiler.stop_trace()
            self.on, self.done = False, True

    def at_step(self, index, n_steps, skip=4):
        """Trace ``n_steps`` steps of a loop, after ``skip`` warm ones."""
        if not self.on and not self.done and index >= skip:
            self.first = index
            self.start()
        elif self.on and index >= self.first + n_steps:
            self.stop()

    def reduced(self, host_prefixes=None):
        if not self.done:
            return None
        return reduce_trace(find_xplane(self.dir), host_prefixes)
