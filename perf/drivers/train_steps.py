"""Driver for training cells: a pool of seeded batches staged on the
device, one compiled step with its state, driven through its first steps
and then through as many steps as fit the window; after the window the
plain reference follows those first steps and the two are compared.

The driver holds the window and the comparison.  What belongs to a model
family — weights, batches, the call of the plain reference, the compiled
step — is the module ``perf/systems/<system>.py`` that the traffic file
names, with ``weights(cfg, seed, device)``, ``batches(cfg, traffic, seed,
rows)``, ``reference_numbers(cfg, traffic, seed, batches, precision)`` and
``build(cfg, traffic, seed, weights, devices)``; the object built has
``place``, ``step``, ``state_norms``, ``grad_projections``, ``delta_norms``,
``exhaust_step_keys`` and ``step_memory_bytes``.

Traffic file keys: ``system``, ``seq_len``, ``per_chip_batch``,
``batch_pool``, ``checked_steps``, ``reference_block_rows``,
``max_in_flight``, ``trace_steps``, ``limits`` (one per number compared),
``controls`` (lower precisions for ``--control``)."""
import gc
import importlib
import json
import time

import numpy as np

from ..harness.projections import projection_gap


def leaf_gaps(program, reference):
    """Per leaf: the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger; sorted, worst first: ``[(gap, leaf)]``."""
    floor = float(np.median(list(reference.values())))
    return sorted(((abs(program[k] - r) / max(r, floor), k)
                   for k, r in reference.items()), reverse=True)


def detail(program, reference):
    """What lies behind the numbers compared (printed, not judged)."""
    out = {"loss_gap_by_step": [abs(p - r) / abs(r) for p, r in zip(
        program["losses"], reference["losses"])]}
    for key in ("grad_norms", "delta_norms"):
        gaps = leaf_gaps(program[key], reference[key])
        vals = [g for g, _k in gaps]
        tot_p = float(np.sqrt(sum(v * v for v in program[key].values())))
        tot_r = float(np.sqrt(sum(v * v for v in reference[key].values())))
        out[key] = {"worst": [(round(g, 6), k) for g, k in gaps[:4]],
                    "p90": float(np.percentile(vals, 90)),
                    "median": float(np.median(vals)),
                    "mean": float(np.mean(vals)),
                    "whole_norm_gap": abs(tot_p - tot_r) / tot_r}
    return out


def compare(program, reference, limits):
    """The numbers compared, each beside its limit:
    ``[(name, value, limit, ok, where), ...]``."""
    loss_gap = max(abs(p - r) / abs(r) for p, r in
                   zip(program["losses"], reference["losses"]))
    grad_gap, grad_leaf = leaf_gaps(program["grad_norms"],
                                    reference["grad_norms"])[0]
    delta_gap, delta_leaf = leaf_gaps(program["delta_norms"],
                                      reference["delta_norms"])[0]
    rows = [("loss_rel_gap_max", loss_gap, None),
            ("grad_projection_gap", projection_gap(
                program["grad_projections"],
                reference["grad_projections"]), None),
            ("grad_norm_gap_worst_leaf", grad_gap, grad_leaf),
            ("param_delta_gap_worst_leaf", delta_gap, delta_leaf)]
    return [(n, float(v), float(limits[n]), bool(v <= limits[n]), where)
            for n, v, where in rows]


def run(cell, args, devices, clock):
    import jax
    from ..harness.compiles import CompileCounter
    from ..harness.device import allocator_peak_bytes

    cfg, tr = cell.config, cell.traffic
    system = importlib.import_module(f"perf.systems.{tr['system']}")
    rows = tr["per_chip_batch"] * len(devices)
    n_check = tr["checked_steps"]
    pool = system.batches(cfg, tr, args.seed, rows)
    checked = [pool[i % len(pool)] for i in range(n_check)]
    stages = {}

    # --- the program: ONE object, driven through its first steps here and
    # handed to the window
    t = time.perf_counter()
    weights = system.weights(cfg, args.seed, devices[0])
    jax.block_until_ready(weights)
    stages["weights_s"] = time.perf_counter() - t
    t = time.perf_counter()
    prog = system.build(cfg, tr, args.seed, weights, devices)
    del weights
    placed = [prog.place(b) for b in pool]
    jax.block_until_ready(placed)
    stages["placement_s"] = time.perf_counter() - t
    t = time.perf_counter()
    program = {"losses": []}
    for i in range(n_check):
        program["losses"].append(float(prog.step(placed[i % len(placed)])))
        if i == 0:
            program["grad_norms"] = prog.state_norms()
            program["grad_projections"] = prog.grad_projections()
    stages["programs_s"] = time.perf_counter() - t
    t = time.perf_counter()
    program["delta_norms"] = prog.delta_norms(
        system.weights(cfg, args.seed, devices[0]))
    stages["readings_s"] = time.perf_counter() - t
    # warm-up: one more step that makes the program refill its key pool,
    # so the refill's small programs are compiled before the window
    t = time.perf_counter()
    prog.exhaust_step_keys()
    float(prog.step(placed[n_check % len(placed)]))
    stages["warmup_s"] = time.perf_counter() - t
    t = time.perf_counter()
    step_bytes, parts = prog.step_memory_bytes(placed[0])
    stages["memory_analysis_s"] = time.perf_counter() - t
    clock.setup_breakdown(stages)

    # --- the window
    tokens_per_step = rows * tr["seq_len"]
    lag, trace = int(tr["max_in_flight"]), None
    if args.trace:
        from ..harness import trace_reduce
        trace = trace_reduce.Tracer(clock.scratch("trace"))
    losses, in_flight = [], []
    with CompileCounter() as compiles:
        t0 = clock.window_opens()
        i = n_check + 1
        while time.perf_counter() - t0 < args.seconds:
            if trace is not None:
                trace.at_step(len(losses) + len(in_flight), tr["trace_steps"])
            in_flight.append(prog.step(placed[i % len(placed)]))
            i += 1
            if len(in_flight) > lag:
                losses.append(float(in_flight.pop(0)))
        losses.extend(float(x) for x in in_flight)
        t1 = time.perf_counter()
        if trace is not None:
            trace.stop()

    # --- the program's memory, read before the yardstick puts anything on
    # the chip: the larger of the allocator's peak and the compiled step's
    # own footprint (on a TPU the allocator's peak leaves a running
    # program's temporaries out)
    allocator_bytes = allocator_peak_bytes(devices)
    print("memory " + json.dumps(dict(
        parts, step_bytes=step_bytes, allocator_peak_bytes=allocator_bytes)),
        flush=True)
    del prog, placed, in_flight
    gc.collect()

    # --- correctness, outside the window and after the program's state is
    # freed: the plain reference follows the first steps
    t = time.perf_counter()
    reference = system.reference_numbers(cfg, tr, args.seed, checked)
    reference_s = time.perf_counter() - t
    print(f"reference: {n_check} steps of {rows} rows, {reference_s:.1f} s",
          flush=True)
    checks = compare(program, reference, tr["limits"])
    print("compare detail " + json.dumps(detail(program, reference)),
          flush=True)
    for prec in tr["controls"] if args.control else ():
        low = system.reference_numbers(cfg, tr, args.seed, checked,
                                       precision=prec)
        print(f"control {prec} detail " + json.dumps(
            detail(low, reference)), flush=True)
        for name, value, limit, ok, where in compare(low, reference,
                                                     tr["limits"]):
            print(f"control {prec}: {name} {value!r} (limit {limit!r}"
                  f"{', at ' + where if where else ''}) -> "
                  f"{'passes' if ok else 'fails'}", flush=True)
    n = len(losses)
    k = max(1, n // 4)
    falls = bool(np.mean(losses[-k:]) < np.mean(losses[:k]))
    finite = bool(np.all(np.isfinite(losses)))
    checks.append(("window_loss_fall", float(np.mean(losses[:k])
                                             - np.mean(losses[-k:])),
                   0.0, falls and finite, None))
    checks.append(("compiles_in_window", float(compiles.n), 0.0,
                   compiles.n == 0, None))
    return {
        "attempted": n, "failed": 0 if finite else n,
        "checks": checks,
        "values": {
            "train_tokens_per_s": n * tokens_per_step / (t1 - t0),
            "steps": n, "tokens_per_step": tokens_per_step,
            "step_seconds": (t1 - t0) / n,
            "compiles_in_window": float(compiles.n),
            "loss_first": losses[0], "loss_last": losses[-1],
        },
        "samples": {},
        "trace": trace,
        "memory_peak_bytes": max(step_bytes, allocator_bytes),
    }
