"""Driver for open-loop serving cells: the traffic design is sent on its
schedule over HTTP/SSE whether or not earlier requests have finished; every
request due inside ``--seconds`` is measured, then the run drains under a
fixed limit.  A request that fails, is refused or is still unfinished at the
limit counts in ``failed`` and has no latency.

After the window, and after the server is closed and freed, the plain
reference follows every finished request.

The driver holds the window.  What belongs to a model family is the module
``perf/systems/<system>.py`` that the traffic file names, with
``weights(cfg, seed, device)``, ``build(cfg, traffic, weights, device,
cache_dir)`` (an object with ``port``, ``model``, ``stats()``, ``close()``)
and ``reference_gaps(cfg, traffic, seed, prompts, served, device,
precisions)``.

Traffic file keys beyond the generator's: ``system``, ``session`` (the
server's geometry), ``drain_limit_s``, ``client_threads``, ``check``
(``pad_to``), ``limits``, ``trace_window_s`` (``[start, length]``),
``controls`` (lower precisions for ``--control``)."""
import gc
import http.client
import importlib
import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor


from ..harness import stats


class _Result:
    __slots__ = ("index", "due", "sent", "token_times", "tokens", "error")

    def __init__(self, index, due):
        self.index, self.due = index, due
        self.sent = None
        self.token_times, self.tokens = [], []
        self.error = None


def _client(port, model, req, res, deadline):
    """Send one request, read its SSE frames as they arrive."""
    body = json.dumps({"model": model, "prompt": req["prompt"],
                       "max_new_tokens": req["max_new_tokens"],
                       "temperature": 0.0, "stream": True})
    conn = http.client.HTTPConnection(
        "127.0.0.1", port, timeout=max(deadline - time.perf_counter(), 0.1))
    try:
        res.sent = time.perf_counter()
        conn.request("POST", "/v1/generate", body,
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        if r.status != 200:
            res.error = f"status {r.status}: {r.read()[:200]!r}"
            return
        while True:
            line = r.fp.readline()
            if not line:
                break
            if not line.startswith(b"data: ") or line.strip() == b"data: [DONE]":
                continue
            now = time.perf_counter()
            frame = json.loads(line[6:])
            if "token" in frame:
                res.token_times.append(now)
                res.tokens.append(int(frame["token"]))
            elif "error" in frame:
                res.error = f"stream error {frame}"
        if res.error is None and len(res.tokens) != req["max_new_tokens"]:
            res.error = (f"{len(res.tokens)} tokens of "
                         f"{req['max_new_tokens']}")
    except (OSError, http.client.HTTPException, ValueError) as e:
        res.error = f"{type(e).__name__}: {e}"
    finally:
        conn.close()


def send_design(port, model, requests, t0, drain_deadline, threads):
    """Pace the design from ``t0``; returns one ``_Result`` per request."""
    results = [_Result(k, t0 + r["due_s"]) for k, r in enumerate(requests)]
    with ThreadPoolExecutor(max_workers=threads) as pool:
        futs = []
        for req, res in zip(requests, results):
            wait = res.due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            futs.append(pool.submit(_client, port, model, req, res,
                                    drain_deadline))
        for f in futs:
            f.result()
    return results


def latencies(results):
    """From the finished requests: ``ttft_ms`` (first token - due),
    ``tpot_ms`` (per request, mean gap after the first token), every
    ``gap_ms``, and how late each send was."""
    ok = [r for r in results if r.error is None]
    out = {"ttft_ms": [(r.token_times[0] - r.due) * 1e3 for r in ok],
           "tpot_ms": [(r.token_times[-1] - r.token_times[0]) * 1e3
                       / (len(r.tokens) - 1) for r in ok
                       if len(r.tokens) > 1],
           "gap_ms": [(b - a) * 1e3 for r in ok
                      for a, b in zip(r.token_times, r.token_times[1:])],
           "gen_late_ms": [(r.sent - r.due) * 1e3 for r in results
                           if r.sent is not None]}
    return out


def run(cell, args, devices, clock):
    import jax
    from ..harness import traffic as traffic_mod
    from ..harness.compiles import CompileCounter
    from ..harness.device import allocator_peak_bytes

    cfg, tr = cell.config, cell.traffic
    system_mod = importlib.import_module(f"perf.systems.{tr['system']}")
    requests = traffic_mod.design(tr, args.seconds, args.seed,
                                  cfg["vocab_size"])
    device = devices[0]
    stages = {}

    t = time.perf_counter()
    weights = system_mod.weights(cfg, args.seed, device)
    jax.block_until_ready(weights)
    stages["weights_s"] = time.perf_counter() - t
    tel = None
    if args.trace:
        import mxnet_tpu as mx
        tel = mx.telemetry
        tel.enable(capacity=500000)
    t = time.perf_counter()
    system = system_mod.build(cfg, tr, weights, device,
                              clock.cache_dir(cell.name))
    del weights
    stages["programs_s"] = time.perf_counter() - t
    t = time.perf_counter()
    warm = _Result(-1, time.perf_counter())
    _client(system.port, system.model,
            {"prompt": [1] * tr["lengths"]["prompt"]["min"],
             "max_new_tokens": 4}, warm, time.perf_counter() + 120)
    if warm.error:
        raise RuntimeError(f"warm-up request failed: {warm.error}")
    stages["warmup_s"] = time.perf_counter() - t
    clock.setup_breakdown(stages)

    # --- the window
    sampler_stop, live, trace = threading.Event(), [], None
    timers, sampler = [], None
    if args.trace:
        from ..harness import trace_reduce
        tel.reset()
        trace = trace_reduce.Tracer(clock.scratch("trace"))
        start, length = tr["trace_window_s"]
        start = min(start, max(args.seconds - length, 0) / 2)
        timers = [threading.Timer(start, trace.start),
                  threading.Timer(start + length, trace.stop)]

        def sample_kv():
            while not sampler_stop.wait(0.5):
                s = system.stats()
                live.append((s["pages_in_use"], s["usable_pages"],
                             s["live_tokens"], s["slots_in_use"]))
        sampler = threading.Thread(target=sample_kv, daemon=True)
        sampler.start()
    with CompileCounter() as compiles:
        t0 = clock.window_opens()
        for th in timers:
            th.start()
        results = send_design(
            system.port, system.model, requests, t0,
            t0 + args.seconds + tr["drain_limit_s"], tr["client_threads"])
        t_end = time.perf_counter()
    sampler_stop.set()
    for th in timers:
        th.cancel()
        th.join()
    if sampler is not None:
        sampler.join()
    if trace is not None:
        trace.stop()
    obs = {}
    if args.trace:
        from mxnet_tpu.telemetry import bus, flight
        snap = tel.snapshot()
        t_mono = time.monotonic() - (time.perf_counter() - t0)
        obs = {"counters": snap["counters"],
               "histogram_quantile": tel.histogram_quantile,
               "histograms": tel.histograms(),
               "spans": [(e[1], e[3] / 1e6, e[4] / 1e6, e[6])
                         for e in bus.events() if e[0] == "X"],
               "flight": [e[:4] for e in flight.events() if e[0] >= t_mono]}
        if live:
            obs["samples_extra"] = {
                "kv_pages_live_pct": [100.0 * a / b for a, b, _t, _c in live],
                "live_tokens_per_row": [t / c for _a, _b, t, c in live if c]}
        tel.disable()
    system.close()
    del system
    gc.collect()
    # the program's memory, read before the yardstick puts anything on
    # the chip
    memory_peak = allocator_peak_bytes(devices)

    # --- correctness, outside the window: the plain reference follows
    # every request the window finished
    lat = latencies(results)
    failed = [r for r in results if r.error is not None]
    for r in failed[:5]:
        print(f"failed request {r.index}: {r.error}", flush=True)
    sample = [r.index for r in results if r.error is None]
    precisions = ("float32",) + (tuple(tr["controls"]) if args.control
                                 else ())
    t = time.perf_counter()
    gaps, n_tokens = system_mod.reference_gaps(
        cfg, tr, args.seed, [requests[i]["prompt"] for i in sample],
        [results[i].tokens for i in sample], device, precisions)
    reference_s = time.perf_counter() - t
    print(f"reference: {len(sample)} requests, {n_tokens} served tokens, "
          f"{reference_s:.1f} s", flush=True)
    limits = tr["limits"]
    sound = gaps["float32"]
    checks = [
        (name, sound[stat], limits[name],
         bool(sample) and sound[stat] <= limits[name], None)
        for name, stat in (("logit_gap_mean", "mean"),
                           ("logit_gap_max", "max"))]
    checks.append(("compiles_in_window", float(compiles.n), 0.0,
                   compiles.n == 0, None))
    print(f"served tokens the reference would not have chosen: "
          f"{sound['moved']} of {n_tokens}", flush=True)
    for p in precisions[1:]:
        fails = [n for n, st in (("logit_gap_mean", "mean"),
                                 ("logit_gap_max", "max"))
                 if gaps[p][st] > limits[n]]
        print(f"control {p}: {json.dumps(gaps[p])} -> "
              f"{'fails ' + ', '.join(fails) if fails else 'PASSES'}",
              flush=True)
    values = {"compiles_in_window": float(compiles.n),
              "window_wall_s": t_end - t0}
    if lat["ttft_ms"]:
        values["ttft_p75_ms"] = stats.percentile(lat["ttft_ms"], 75)
        values["ttft_mean_ms"] = stats.mean(lat["ttft_ms"])
        print("latency " + json.dumps({
            "ttft_ms": {q: round(stats.percentile(lat["ttft_ms"], q), 3)
                        for q in (50, 75, 90, 100)},
            "ttft_mean_ms": round(values["ttft_mean_ms"], 3),
            "tpot_ms": {q: round(stats.percentile(lat["tpot_ms"], q), 3)
                        for q in (10, 50, 90)},
            "gap_mean_ms": round(stats.mean(lat["gap_ms"]), 3)}), flush=True)
    if lat["tpot_ms"]:
        values["tpot_p50_ms"] = stats.median(lat["tpot_ms"])
    n_tok = sum(len(r.tokens) for r in results if r.error is None)
    values["out_tokens_per_s"] = n_tok / (t_end - t0)
    samples = dict(lat)
    samples.update(obs.pop("samples_extra", {}))
    return {"attempted": len(requests), "failed": len(failed),
            "checks": checks, "values": values, "samples": samples,
            "trace": trace, "obs": obs, "memory_peak_bytes": memory_peak}
