"""Time one sublayer's hyper-connections on the chip, the two kernels
(``ops.pallas_kernels.hc_pre`` + ``hc_post``) against the definition
(``ops.hyper_connection``, XLA's fusions), with no model around them:

    python tools/hc_probe.py [--tokens 1 6 32 512 2048] [--depth 40] [--live 6]

A chain of ``--depth`` sublayers at Xing4.0's widths (4 streams of 3,584)
around a trivial ``f``, so that what is timed is what a step's 40 sublayers
pay: launches, ``phi``, the streams.  Prints one JSON line a token count:
microseconds a sublayer for each form, and how far the kernels' ``u``,
coefficients and written streams lie from the definition's on the same
inputs.  ``--live N`` tells the kernels that only the first ``N`` rows of a
step's block (8 to 127 tokens) are not padding.  Needs the chip; fails
without one.
"""
import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mxnet_tpu.ops import hyper_connection as hc  # noqa: E402
from mxnet_tpu.ops import pallas_kernels as pk  # noqa: E402

N, C, ITERS, EPS, CLAMP = 4, 3584, 20, 1e-6, (-30.0, 30.0)


def halves(kernel, live=None):
    """``(pre, post)`` of one sublayer, both handing over a coefficient
    tile."""
    if kernel:
        return (lambda X, phi_t, a, b: pk.hc_pre(
            X, phi_t, a, b, live, iters=ITERS, eps=EPS, clamp=CLAMP),
            pk.hc_post)

    def pre(X, phi_t, a, b):
        h_pre, h_post, h_res = hc.hc_coefficients(
            X, {"phi": phi_t.T, "a": a, "b": b}, ITERS, EPS, CLAMP)
        return hc.hc_read(X, h_pre), hc.coef_tile(h_pre, h_post, h_res)

    def post(X, coef, y):
        _, h_post, h_res = hc.coef_parts(coef, X.shape[:-2], N)
        return hc.hc_write(X, h_res, h_post, y)
    return pre, post


def chain(kernel, depth, live):
    pre, post = halves(kernel, live)

    def run(X, phis, a, b):
        for phi_t in phis:
            u, coef = pre(X, phi_t, a, b)
            X = post(X, coef, 0.5 * u)
        return X
    return jax.jit(run)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, nargs="+",
                    default=[1, 6, 32, 512, 2048])
    ap.add_argument("--depth", type=int, default=40)
    ap.add_argument("--live", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"hc_probe needs the chip, found {dev.platform}")
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
    phis = list(0.02 * jax.random.normal(
        keys[0], (args.depth, N * (N + 2), N * C), jnp.float32))
    a = jnp.full((3,), 0.3, jnp.float32)
    b = jnp.zeros((N * (N + 2),)).at[2 * N:].set(
        1.5 * jnp.eye(N).reshape(-1)) + 0.1 * jax.random.normal(
            keys[1], (N * (N + 2),))
    for t in args.tokens:
        X = jax.random.normal(keys[2], (t, N, C), jnp.float32)
        live = None
        if args.live is not None and args.live < t < 128:
            live = jnp.arange(t) < args.live
        out = {"tokens": t, "live": args.live if live is not None else t,
               "depth": args.depth, "device": dev.device_kind}
        for name, kernel in (("kernels", True), ("definition", False)):
            fn = chain(kernel, args.depth, live)
            fn(X, phis, a, b).block_until_ready()
            reps = 20 if t <= 128 else 5
            t0 = time.perf_counter()
            for _ in range(reps):
                last = fn(X, phis, a, b)
            last.block_until_ready()
            out[name + "_us_a_sublayer"] = round(
                (time.perf_counter() - t0) / reps / args.depth * 1e6, 2)
        def once(kernel):
            pre, post = halves(kernel, live)
            u, coef = pre(X, phis[0], a, b)
            return u, coef, post(X, coef, 0.5 * u)

        want, got = jax.jit(lambda: once(False))(), \
            jax.jit(lambda: once(True))()
        rows = slice(None) if live is None else slice(0, args.live)
        for key, w, g in zip(("u", "coef", "written"), want, got):
            out["max_abs_diff_" + key] = float(jnp.abs(w - g)[rows].max())
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
