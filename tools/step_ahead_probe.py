"""Does the runtime run a decode step that was launched behind another one
back to back with it?  One benchmark cell's step programs, no traffic:

    python tools/step_ahead_probe.py --workload mimo_v2_5_ep16.mixed_lengths \
        --seed 1 --rows 8 --context 1500 --steps 50

builds the cell's system as ``perf/run.py`` does, gives ``--rows`` rows a
slot with ``--context`` tokens of (unwritten) context, and times the same
``--steps`` decode steps three ways through ``DecodeRuntime``:

- ``sync``: ``step()``: launch, collect, launch, collect;
- ``ahead``: the decode loop's pipeline: ``launch`` of step n+1 on step n's
  device tokens, then ``collect`` of step n;
- ``queued``: every step launched on the one before it, one collect at the
  end: what the launches alone cost (or the device, whichever is larger).

A step launched ahead should cost the LARGER of the device's step and the
launch, not their sum.  One JSON line a mode, milliseconds a step.  A
builder's tool (ISSUE 36); needs the chip for numbers that mean anything,
runs anywhere.
"""
import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--context", type=int, default=1500)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--root", default=ROOT,
                    help="where BENCHMARK.json and perf/ are read from")
    args = ap.parse_args()
    import numpy as np
    from mxnet_tpu.runtime import compile_cache
    from mxnet_tpu.serving.decode import pages_needed
    from perf.harness.spec import Cell
    import jax
    cell = Cell(args.workload, root=args.root)
    device = jax.devices()[0]
    compile_cache()
    tr = cell.traffic
    system_mod = importlib.import_module("perf.systems." + tr["system"])
    t0 = time.perf_counter()
    system = system_mod.build(
        cell.config, tr, system_mod.weights(cell.config, args.seed, device),
        device, os.path.join(ROOT, ".aot_cache", "perf", cell.name))
    rt = system.session.runtime
    cache = rt.cache
    print(json.dumps({"device": device.device_kind,
                      "setup_s": round(time.perf_counter() - t0, 1)}),
          flush=True)
    n, steps = args.rows, args.steps
    b = rt.batch_bucket_for(n)
    slots = [cache.alloc(pages_needed(args.context, 3 * steps + 2,
                                      cache.page_tokens)) for _ in range(n)]
    rng = np.random.RandomState(args.seed % (2 ** 31))
    first = np.zeros((b,), "int32")
    first[:n] = rng.randint(1, cell.config["vocab_size"], n)
    tables = np.zeros((b, cache.table_width), "int32")
    keys = np.zeros((b, 2), "uint32")
    temps = np.zeros((b,), "float32")
    for r, slot in enumerate(slots):
        tables[r] = slot.page_table
        keys[r] = (0, r + 1)

    def rest(i, base):
        """Step ``i``'s arguments behind its tokens."""
        positions = np.zeros((b,), "int32")
        positions[:n] = args.context + base + i
        idx = np.zeros((b,), "int32")
        idx[:n] = 1 + base + i
        return positions, tables, keys, idx, temps

    def sync(base):
        tokens, per = first, []
        for i in range(steps):
            t = time.perf_counter()
            tokens = rt.step(tokens, *rest(i, base))
            per.append(time.perf_counter() - t)
        return per, {}

    def ahead(base):
        per, launch = [], []
        t = time.perf_counter()
        flight = rt.launch(first, *rest(0, base))
        for i in range(1, steps):
            t1 = time.perf_counter()
            nxt = rt.launch(flight.tokens, *rest(i, base))
            launch.append(time.perf_counter() - t1)
            rt.collect(flight)
            flight = nxt
            now = time.perf_counter()
            per.append(now - t)
            t = now
        rt.collect(flight)
        return per, {"launch_p50_ms": round(1e3 * _median(launch), 3)}

    def queued(base):
        t = time.perf_counter()
        flight = rt.launch(first, *rest(0, base))
        for i in range(1, steps):
            flight = rt.launch(flight.tokens, *rest(i, base))
        launched = time.perf_counter() - t
        rt.collect(flight)
        total = time.perf_counter() - t
        return [total / steps] * steps, {
            "launches_ms_a_step": round(1e3 * launched / steps, 3)}

    sync(0)                     # every mode once, unmeasured
    for rep in range(2):
        for name, mode, base in (("sync", sync, 0), ("ahead", ahead, steps),
                                 ("queued", queued, 2 * steps)):
            t = time.perf_counter()
            per, more = mode(base)
            total = time.perf_counter() - t
            print(json.dumps(dict(
                mode=name, rep=rep, rows=n, bucket=b, steps=steps,
                step_p50_ms=round(1e3 * _median(per), 3),
                step_mean_ms=round(1e3 * total / steps, 3), **more)),
                flush=True)
    for slot in slots:
        cache.free(slot)
    system.close()


if __name__ == "__main__":
    main()
