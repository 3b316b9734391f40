"""Time the causal attention of one padded prompt of a latent-attention
block on the chip, the kernel (``ops.pallas_kernels.mla_prefill_attention``)
against the definition (``LatentMoELM._scores_chain``: every head's ``(S, S)``
float32 scores, a ``where``, one softmax, ``p . v``; what a prefill program
lowered for the CPU runs, and what it ran on the chip before the kernel),
with no model around them:

    python tools/mla_prefill_probe.py [--heads 32 64] [--lengths 512 1024
        1536 2048] [--blocks 256x512 512x1024] [--layers 8]

Operands at the published widths (128-wide unrotated queries, keys and
values, 64-wide rotated ones), seeded normal values in bfloat16; ``--layers``
calls in one program, each layer's queries made from the layer's before.
Prints one JSON line a form: microseconds a layer, the causal score elements
a second, the share of the MXU's peak the two products reach
(``2 x heads x S (S + 1) / 2 x (nope + rope + v)`` operations: the live
half, not the masked one), and how far the kernel's output lies from the
definition's on the same inputs.  Needs the chip; fails without one.
"""
import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mxnet_tpu.ops.pallas_kernels import mla_prefill_attention  # noqa: E402
from mxnet_tpu.serving.decode import LatentMoELM  # noqa: E402
from perf.harness.device import peaks_for  # noqa: E402

NOPE, ROPE, WIDTH = 128, 64, 128


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", type=int, nargs="+", default=[32, 64])
    ap.add_argument("--lengths", type=int, nargs="+",
                    default=[512, 1024, 1536, 2048])
    ap.add_argument("--blocks", nargs="+", default=[],
                    help="query x key block sizes of the kernel, beside "
                    "the ones it picks")
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"mla_prefill_probe needs the chip, found {dev.platform}")
    L = args.layers
    peak = peaks_for(dev.device_kind)["bf16_flops_per_s"]
    for H in args.heads:
        net = LatentMoELM(num_heads=H, qk_nope_head_dim=NOPE,
                          qk_rope_head_dim=ROPE, v_head_dim=WIDTH,
                          num_layers=1)
        for S in args.lengths:
            keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
            bf = jnp.bfloat16
            q_nope = jax.random.normal(keys[0], (1, S, H, NOPE), bf)
            q_rope = jax.random.normal(keys[1], (1, S, H, ROPE), bf)
            kv = jax.random.normal(keys[2], (1, S, H, NOPE + WIDTH), bf)
            kr = jax.random.normal(keys[3], (1, S, ROPE), bf)
            causal = jnp.tril(jnp.ones((S, S), bool))

            def definition(q_nope, q_rope, kv, kr):
                return net._scores_chain(q_nope, q_rope, kv, kr, causal)

            def kernel(bq, bk):
                return lambda *ops: mla_prefill_attention(
                    *ops, scale=net._scale, block_q=bq,
                    block_k=bk).astype(jnp.float32)

            def chain(attend):
                def run(q_nope, q_rope, kv, kr):
                    first = None
                    for _ in range(L):
                        o = attend(q_nope, q_rope, kv, kr)
                        first = o if first is None else first
                        q_nope = q_nope + (1e-3 * o.reshape(
                            q_nope.shape)).astype(bf)
                    return q_nope, first
                return jax.jit(run)

            forms = [("definition", definition),
                     ("kernel", kernel(None, None))]
            for blocks in args.blocks:
                bq, bk = (int(x) for x in blocks.split("x"))
                if S % bq == 0 and S % bk == 0:
                    forms.append((f"kernel-{bq}x{bk}", kernel(bq, bk)))
            want = None
            elements = H * S * (S + 1) // 2
            for name, attend in forms:
                fn = chain(attend)
                _q, first = fn(q_nope, q_rope, kv, kr)
                first.block_until_ready()
                reps = 5
                t0 = time.perf_counter()
                for _ in range(reps):
                    last = fn(q_nope, q_rope, kv, kr)[0]
                last.block_until_ready()
                us = (time.perf_counter() - t0) / reps / L * 1e6
                out = {"form": name, "heads": H, "S": S, "layers": L,
                       "us_a_layer": round(us, 1),
                       "causal_gelements_per_s": round(
                           elements / us / 1e3, 1),
                       "mxu_share_pct": round(
                           100 * 2 * elements * (NOPE + ROPE + WIDTH)
                           / (us * 1e-6) / peak, 1),
                       "device": dev.device_kind}
                if want is None:
                    want = first
                else:
                    out["max_abs_diff"] = float(jnp.abs(first - want).max())
                    out["max_abs_definition"] = float(jnp.abs(want).max())
                print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
