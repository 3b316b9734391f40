"""Find a serving cell's knee: a sweep of paced rates in ONE process on the
chip (one set-up), one window a rate, one ROW line each.  The knee is the
highest rate whose backlog does not grow.

    python perf/tools/serve_probe.py --workload gpt2_medium.chat_paced \
        --seed 1 --seconds 50 --rates 0.25 0.4 0.5 0.6 0.8 1.0 1.2
"""
import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def window(system, cell, traffic, seconds, seed):
    from mxnet_tpu.telemetry import flight
    from perf.drivers import serve_open_loop as drv
    from perf.harness import stats, traffic as traffic_mod
    requests = traffic_mod.design(traffic, seconds, seed,
                                  cell.config["vocab_size"])
    flight.reset()
    t0 = time.perf_counter()
    results = drv.send_design(system.port, system.model, requests, t0,
                              t0 + seconds + traffic["drain_limit_s"],
                              traffic["client_threads"])
    t1 = time.perf_counter()
    lat = drv.latencies(results)
    ladder = traffic["session"]["batch_buckets"]
    rows = [v for _t, n, _d, v, _tid in flight.events()
            if n == "decode.step" and v]
    buckets = {}
    for v in rows:
        b = next(x for x in ladder if x >= v)
        buckets[b] = buckets.get(b, 0) + 1
    row = {
        "rate": traffic["arrivals"]["rate_rps"], "n": len(requests),
        "failed": sum(r.error is not None for r in results),
        "ttft_p50": stats.percentile(lat["ttft_ms"], 50),
        "ttft_p75": stats.percentile(lat["ttft_ms"], 75),
        "ttft_max": max(lat["ttft_ms"]),
        "ttft_last_quarter_p50": stats.percentile(
            lat["ttft_ms"][-max(len(requests) // 4, 1):], 50),
        "tpot_p50": stats.median(lat["tpot_ms"]),
        "rows_per_step": stats.mean(rows), "steps": len(rows),
        "bucket_share": {k: round(v / len(rows), 3)
                         for k, v in sorted(buckets.items())},
        "drain_s": t1 - t0 - requests[-1]["due_s"],
        "out_tok_s": sum(len(r.tokens) for r in results) / (t1 - t0)}
    print("ROW " + json.dumps(row), flush=True)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=50)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    from mxnet_tpu.runtime import compile_cache
    from perf.harness import device as device_mod
    from perf.harness.spec import Cell
    cell = Cell(args.workload)
    devices = device_mod.require_chips(cell.chips)
    compile_cache()
    tr = cell.traffic
    system_mod = importlib.import_module("perf.systems." + tr["system"])
    system = system_mod.build(
        cell.config, tr, system_mod.weights(cell.config, args.seed,
                                            devices[0]),
        devices[0], os.path.join(ROOT, ".aot_cache", "perf", cell.name))
    rows = [window(system, cell,
                   dict(tr, arrivals=dict(tr["arrivals"], rate_rps=rate)),
                   args.seconds, args.seed) for rate in args.rates]
    # the last quarter's requests wait no more than twice what the run's
    # median did (and half a second)
    sustained = [r["rate"] for r in rows if not r["failed"]
                 and r["ttft_last_quarter_p50"] < 2 * r["ttft_p50"] + 500]
    print(f"KNEE {max(sustained, default=None)}", flush=True)
    system.close()


if __name__ == "__main__":
    main()
