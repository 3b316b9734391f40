"""Record one small device trace of a toy scheduler loop: a thread that
turns four times through annotated host phases named as the decode
scheduler names its own (``decode.boundary`` > ``decode.admit``,
``decode.step.prepare``, ``decode.step`` > ``.dispatch`` + ``.fetch``,
``decode.step.fanout``; then ``decode.idle``), each host phase a sleep of
known length around one jitted program.  Python tracer off.  Run on the chip:
``python perf/tools/phase_probe.py chiprun_out/phase_probe``.  The trace it
wrote is ``perf/testdata/toy_phases_v5e.xplane.pb``, which the test of
``perf/harness/idle_phases.py`` reads."""
import glob
import os
import sys
import threading
import time

SLEEPS = {"decode.admit": 0.002, "decode.step.prepare": 0.003,
          "decode.step.fanout": 0.001, "decode.idle": 0.010}
TURNS = 4


def main(out):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import TraceAnnotation as ann

    @jax.jit
    def step(x, w):
        def body(_i, y):
            return jnp.tanh(y @ w) * 0.5
        return jax.lax.fori_loop(0, 8, body, x).sum(axis=1)

    x = jnp.ones((4096, 4096), jnp.bfloat16)
    w = jnp.ones((4096, 4096), jnp.bfloat16)
    np.asarray(step(x, w))

    def loop():
        for turn in range(TURNS):
            with ann("decode.boundary", active=1):
                with ann("decode.admit"):
                    time.sleep(SLEEPS["decode.admit"])
                with ann("decode.step.prepare", rows=1):
                    time.sleep(SLEEPS["decode.step.prepare"])
                with ann("decode.step", batch=1):
                    with ann("decode.step.dispatch"):
                        y = step(x, w)
                    with ann("decode.step.fetch"):
                        np.asarray(y)
                with ann("decode.step.fanout", rows=1):
                    time.sleep(SLEEPS["decode.step.fanout"])
            with ann("decode.idle"):
                time.sleep(SLEEPS["decode.idle"])

    os.makedirs(out, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    th = threading.Thread(target=loop, name="toy-scheduler")
    th.start()
    th.join()
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(out, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    print("trace", path, os.path.getsize(path), "bytes")
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from perf.harness import idle_phases, trace_reduce
    red = trace_reduce.reduce_trace(path, host_prefixes=("decode.",))
    print("lines", sorted({th for th, _n, _s, _d in red.host}))
    print("busy_s", red.busy_s, "window_s", red.window_s)
    print("by_phase", idle_phases.by_phase(red))


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/phase_probe")
