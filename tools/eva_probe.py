"""Time one decode step's EVA attention on the chip, the kernel
(``ops.pallas_kernels.eva_attention``) against the definition (a row's whole
ring and every reserved summary row gathered, then ``EvaLM.attend_row``: what
a program lowered for the CPU runs, and what the step ran on the chip before
the kernel), with no model around them:

    python tools/eva_probe.py [--positions 5220 7000 3000] [--rows 8]
        [--block-pages 4 8 16] [--layers 16]

Pools at ``evabyte_pp2.doc_bytes``'s size (16 layers, 8 slots' rings of 2,048
entries of 32 heads of 128, 385 pages of 16 summary rows; 6.4 GB of
bfloat16), seeded normal values; a batch of ``--rows`` rows of which the
first ``len(--positions)`` are live at those positions; ``--layers`` calls in
one program, each layer's query made from the layer's before.  Prints one
JSON line a form: microseconds a layer, the live bytes a layer over that
time, and how far the kernel's output lies from the definition's on the same
inputs.  Needs the chip; fails without one.
"""
import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mxnet_tpu.ops.pallas_kernels import eva_attention  # noqa: E402
from mxnet_tpu.serving.decode import EvaLM  # noqa: E402
from mxnet_tpu.serving.decode.kv_format import PageFormat  # noqa: E402

HEADS, WIDTH, WINDOW, CHUNK, PAGE = 32, 128, 2048, 16, 16
PAGES, ROW_PAGES, SLOTS = 385, 48, 8


def normal(key, shape):
    """Seeded bfloat16 values of ``shape``, a leading index at a time (a
    ring pool whole would be made through 4.8 GB of float32)."""
    return jax.jit(lambda keys: jax.lax.map(
        lambda k: jax.random.normal(k, shape[1:], jnp.bfloat16), keys))(
            jax.random.split(key, shape[0]))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--positions", type=int, nargs="+",
                    default=[5220, 7000, 3000])
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--block-pages", type=int, nargs="+", default=[8])
    ap.add_argument("--layers", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"eva_probe needs the chip, found {dev.platform}")
    L, B, live = args.layers, args.rows, len(args.positions)
    net = EvaLM(vocab_size=16, hidden_size=HEADS * WIDTH, num_layers=L,
                num_attention_heads=HEADS, intermediate_size=16,
                window_size=WINDOW, chunk_size=CHUNK, num_pred_heads=1,
                max_length=ROW_PAGES * PAGE * CHUNK)
    fmt = PageFormat(net.cache_layout(), page_size=PAGE)
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 5)
    pools = tuple(normal(k, shape) for k, shape in zip(keys, (
        (L, PAGES, PAGE, HEADS, WIDTH),) * 2 + (
        (L, SLOTS + 1, WINDOW, HEADS, WIDTH),) * 2))
    q = jax.random.normal(keys[4], (B, HEADS, WIDTH), jnp.float32)
    # a live row's pages and slot as the cache hands them out; 0: padded
    tables = jnp.zeros((B, ROW_PAGES), jnp.int32).at[:live].set(
        1 + jnp.arange(live * ROW_PAGES).reshape(live, ROW_PAGES))
    rows = jnp.zeros((B,), jnp.int32).at[:live].set(1 + jnp.arange(live))
    positions = jnp.zeros((B,), jnp.int32).at[:live].set(
        jnp.asarray(args.positions, jnp.int32))
    columns = sum(p % WINDOW + 1 + p // WINDOW * (WINDOW // CHUNK)
                  for p in args.positions)
    live_bytes = columns * 2 * HEADS * WIDTH * 2

    def definition(pools, i, q):
        went = positions % WINDOW
        closed = positions // WINDOW * (WINDOW // CHUNK)
        sk, sv = fmt.read(pools, i, tables)
        return jnp.stack([net.attend_row(
            q[b], *fmt.state.read(pools, i, rows[b]),
            jnp.arange(WINDOW) <= went[b], sk[b], sv[b],
            jnp.arange(sk.shape[1]) < closed[b]) for b in range(B)])

    def kernel(block_pages):
        return lambda pools, i, q: eva_attention(
            q, *pools, i, tables, rows, positions, row_tokens=CHUNK,
            block_pages=block_pages)

    def chain(attend):
        def run(pools, q):
            first = None
            for i in range(L):
                o = attend(pools, i, q)
                first = o if first is None else first
                q = q + 1e-3 * o
            return q, first
        return jax.jit(run)

    want = None
    forms = [("definition", definition)] + [
        (f"kernel-bp{bp}", kernel(bp)) for bp in args.block_pages]
    for name, attend in forms:
        fn = chain(attend)
        _q, first = fn(pools, q)
        first.block_until_ready()
        reps = 10
        t0 = time.perf_counter()
        for _ in range(reps):
            last = fn(pools, q)[0]
        last.block_until_ready()
        us = (time.perf_counter() - t0) / reps / L * 1e6
        out = {"form": name, "rows": B, "positions": args.positions,
               "layers": L, "us_a_layer": round(us, 2),
               "live_mb_a_layer": round(live_bytes / 1e6, 2),
               "live_gb_per_s": round(live_bytes / us / 1e3, 1),
               "device": dev.device_kind}
        if want is None:
            want = first
        else:
            out["max_abs_diff"] = float(jnp.abs(first - want)[:live].max())
            out["max_abs_padded"] = float(jnp.abs(first[live:]).max()) \
                if live < B else 0.0
            out["max_abs_definition"] = float(jnp.abs(want[:live]).max())
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
