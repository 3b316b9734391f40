"""Record one small device trace of a toy program and print how the
profiler lays it out (planes, lines, event names, stats).  Run on the chip:
``python perf/tools/trace_probe.py chiprun_out/probe``.  The trace it wrote
is the one kept under ``perf/testdata`` for the reducer's test."""
import glob
import os
import sys
import time


def main(out):
    import jax
    import jax.numpy as jnp
    print("env JAX_COMPILATION_CACHE_DIR =",
          os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    print("devices", jax.devices())
    print("memory_stats", jax.devices()[0].memory_stats())

    @jax.jit
    def step(x, w):
        with jax.named_scope("toy_matmul"):
            y = x @ w
        with jax.named_scope("toy_tail"):
            return jnp.tanh(y) * 0.5 + x.sum()

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    w = jnp.ones((1024, 1024), jnp.bfloat16)
    step(x, w).block_until_ready()
    os.makedirs(out, exist_ok=True)
    jax.profiler.start_trace(out)
    for i in range(3):
        with jax.profiler.TraceAnnotation("toy.host_step", step=i):
            x = step(x, w)
            x.block_until_ready()
        with jax.profiler.TraceAnnotation("toy.host_sleep"):
            time.sleep(0.01)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    print("trace", path, os.path.getsize(path), "bytes")
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events), "events")
            for ev in events[:6]:
                stats = {k: (str(v)[:60]) for k, v in list(ev.stats)[:8]}
                print("     EV", repr(ev.name)[:70], ev.start_ns,
                      ev.duration_ns, stats)


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/probe")
