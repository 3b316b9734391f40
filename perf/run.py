"""The benchmark's one command:

    python3 perf/run.py --workload <config>.<traffic> --seed N \
        --seconds S --trace 0|1

One new process per run.  Earlier lines of standard output say what set-up
cost and show each number compared beside its limit; the last line is the
result object and nothing else.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics, the device's busy
time and the breakdown.  Exits non-zero, printing no result, where JAX finds
no accelerator or fewer chips than the cell asks for.
"""
import time

_T0 = time.perf_counter()       # process start, as near as Python can say

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Clock:
    """Set-up's bookkeeping: where the window opens, what set-up held, and
    the directories a run may write (all inside the checkout)."""

    def __init__(self, t0, root):
        self.t0, self.root = t0, root
        self.setup_s = None

    def setup_breakdown(self, stages):
        """Print the stages of set-up.  (The plain reference runs after the
        window, so none of its seconds are in here.)"""
        line = {k: round(v, 3) for k, v in stages.items()}
        line["imports_and_data_s"] = round(
            time.perf_counter() - self.t0 - sum(stages.values()), 3)
        print("setup " + json.dumps(line), flush=True)

    def window_opens(self):
        now = time.perf_counter()
        self.setup_s = now - self.t0
        return now

    def scratch(self, name):
        return os.path.join(self.root, ".perf_out", name)

    def cache_dir(self, cell_name):
        """Fixed path of the AOT program cache of one cell."""
        return os.path.join(self.root, ".aot_cache", "perf", cell_name)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # builder's tools only; the driver never passes these
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also put the lower-precision control in the "
                         "program's place and print what it reads")
    return ap.parse_args(argv)


def result_line(cell, args, out, clock, devices):
    """The last line: metrics by ``--trace``, device, breakdown."""
    from perf.harness import device as device_mod
    from perf.harness import readers
    dev = device_mod.describe(devices, out["memory_peak_bytes"])
    correct = all(ok for _n, _v, _l, ok, _w in out["checks"]) \
        and dev["platform"] != "cpu"
    line = {"correct": bool(correct), "attempted": int(out["attempted"]),
            "failed": int(out["failed"]), "metrics": {}, "device": dev}
    values = dict(out["values"], setup_s=clock.setup_s)
    if not args.trace:
        for m in cell.end_to_end():
            if values.get(m["name"]) is not None:
                line["metrics"][m["name"]] = {
                    "value": float(values[m["name"]]), "unit": m["unit"]}
        return line
    reduced = out["trace"].reduced() if out.get("trace") else None
    obs = dict(out.get("obs") or {}, samples=out["samples"], values=values,
               trace=reduced, cell=cell, chips=len(devices),
               memory_peak_bytes=dev["memory_peak_bytes"],
               peaks=device_mod.peaks_for(dev["kind"])
               if dev["platform"] != "cpu" else None)
    for m in cell.per_layer():
        v = readers.read_metric(m["name"], obs)
        if v is not None:
            line["metrics"][m["name"]] = {"value": float(v),
                                          "unit": m["unit"]}
    if reduced is not None:
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
        ops = sorted(reduced.op_seconds().items(), key=lambda kv: -kv[1])
        line["breakdown"] = {
            "device_ops": [[k, v] for k, v in ops[:10]],
            "idle_gaps": [[n, s] for n, s in reduced.idle_gaps(10)]}
    return line


def main(argv=None):
    args = parse(argv if argv is not None else sys.argv[1:])
    from perf.harness import device as device_mod
    from perf.harness.spec import Cell
    cell = Cell(args.workload)
    try:
        devices = device_mod.require_chips(cell.chips)
    except device_mod.NoAccelerator as e:
        print(f"perf/run.py: {e}", file=sys.stderr)
        return 3
    # the program's own switch for JAX's persistent cache: where
    # JAX_COMPILATION_CACHE_DIR is set it is used, else <checkout>/.jax_cache
    from mxnet_tpu.runtime import compile_cache
    cache = compile_cache()
    clock = Clock(_T0, ROOT)
    driver = importlib.import_module(
        f"perf.drivers.{cell.traffic['driver']}")
    out = driver.run(cell, args, devices, clock)
    for name, value, limit, ok, where in out["checks"]:
        print(f"check {name}: {value!r} (limit {limit!r}"
              f"{', at ' + where if where else ''}) -> "
              f"{'ok' if ok else 'NOT CORRECT'}", flush=True)
    print("compile_cache " + json.dumps(cache.stats()), flush=True)
    line = result_line(cell, args, out, clock, devices)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
