"""Lane-aligned Pallas BN-stats kernel vs XLA's fused reduction (the r3
post-mortem's prescribed experiment — VERDICT r4 item 2).

The r3 attempt lost 2x because its (C, HW) blocks reduced ALONG the lane
dimension (cross-lane tree per block).  The lane-aligned design here never
does a wide lane reduction: each grid step reads a (C, LW) tile of the
NCHW activation (C on sublanes, a lane-multiple chunk of HW on lanes) and
adds its LW/128 column-slices ELEMENTWISE into persistent (C, 128)
sum/sumsq accumulators; the only cross-lane fold is the final (C, 128) →
(C,) pass over the tiny accumulator, done once in XLA.

Measures both against the framework's current one-pass XLA formulation
(shifted E[x], E[x^2] — ops/nn.py batch_norm) on all nine ResNet-50 BN
activation geometries, batch 32, bf16 activations / f32 statistics.

Not measured on the attached chip in this round.  An earlier round's
record (since deleted with the transport it was taken through) found the
kernel numerically exact and competitive per shape but no better than
XLA's fused reduction over the whole ResNet-50 inventory: the kernel
cannot fuse with the producing convolution, which is where a real win
would have to come from.

Usage: python benchmark/pallas_bn_stats.py
"""
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from bench import _time_blocks

    LANES = 128

    def bn_stats_kernel(x_ref, sum_ref, sq_ref, *, lw, hw):
        """One (n, hw-chunk) program.  x block: (1, C, LW); accumulators
        (C, 128) persist across the whole grid.  The tail chunk masks
        positions ≥ HW (HW need not be a lane multiple — 56² = 24.5×128)."""
        j = pl.program_id(1)
        step = pl.program_id(0) * pl.num_programs(1) + j

        @pl.when(step == 0)
        def _init():
            sum_ref[...] = jnp.zeros_like(sum_ref)
            sq_ref[...] = jnp.zeros_like(sq_ref)

        x = x_ref[0].astype(jnp.float32)          # (C, LW)
        c = x.shape[0]
        pos = j * lw + jax.lax.broadcasted_iota(jnp.int32, (1, lw), 1)
        x = jnp.where(pos < hw, x, 0.0)
        xs = x.reshape(c, lw // LANES, LANES)
        # elementwise adds over the chunk axis — no lane reduction
        s = jnp.sum(xs, axis=1)                   # (C, 128): sublane-safe
        q = jnp.sum(xs * xs, axis=1)
        sum_ref[...] += s
        sq_ref[...] += q

    def pallas_stats(x, lw):
        n, c, h, w = x.shape
        hw = h * w
        assert lw % LANES == 0, lw
        x3 = x.reshape(n, c, hw)
        grid = (n, (hw + lw - 1) // lw)
        out_shape = [jax.ShapeDtypeStruct((c, LANES), jnp.float32),
                     jax.ShapeDtypeStruct((c, LANES), jnp.float32)]
        s, q = pl.pallas_call(
            functools.partial(bn_stats_kernel, lw=lw, hw=hw),
            grid=grid,
            in_specs=[pl.BlockSpec((1, c, lw),
                                   lambda i, j: (i, 0, j))],
            out_specs=[pl.BlockSpec((c, LANES), lambda i, j: (0, 0)),
                       pl.BlockSpec((c, LANES), lambda i, j: (0, 0))],
            out_shape=out_shape,
        )(x3)
        cnt = n * hw
        mean = jnp.sum(s, axis=1) / cnt           # tiny final fold
        var = jnp.maximum(jnp.sum(q, axis=1) / cnt - mean * mean, 0.0)
        return mean, var

    def xla_stats(x):
        # the framework's current formulation (ops/nn.py batch_norm):
        # one pass, f32 accumulation, E[x^2]-E[x]^2
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=(0, 2, 3))
        sq = jnp.mean(x32 * x32, axis=(0, 2, 3))
        return mean, jnp.maximum(sq - mean * mean, 0.0)

    shapes = [  # every distinct BN activation geometry in ResNet-50 @224
        (32, 64, 112, 112),
        (32, 64, 56, 56), (32, 256, 56, 56),
        (32, 128, 28, 28), (32, 512, 28, 28),
        (32, 256, 14, 14), (32, 1024, 14, 14),
        (32, 512, 7, 7), (32, 2048, 7, 7),
    ]
    rng = np.random.RandomState(0)
    results = {}

    def time_fn(fn, x, reps=600, blocks=5):
        # sub-millisecond kernels: the block must dwarf the sync fetch
        # that ends it or the subtraction noise swamps the signal
        c = jax.jit(fn).lower(x).compile()
        c(x)                            # compile + warm
        holder = {}

        def tblock():
            for _ in range(reps):
                holder["o"] = c(x)

        tblock()

        def tsync():
            return float(np.asarray(holder["o"][0][0]))

        ts = _time_blocks(tblock, blocks, tsync)
        return float(np.median(ts)) / reps

    total_xla = total_pl = 0.0
    for shp in shapes:
        n, c, h, w = shp
        hw = h * w
        # largest lane-multiple chunk that divides HW (HW of 112²=12544 =
        # 98*128; 56²=3136=24.5*128 → use 56*56 rows? fall back to a
        # divisor search)
        # largest lane-multiple chunk ≤ HW that divides it, else a padded
        # 2048 chunk with in-kernel tail masking
        lw = None
        for cand in (2048, 1792, 1568, 1024, 896, 784, 512, 448, 392, 256,
                     128):
            if hw % cand == 0 and cand % LANES == 0:
                lw = cand
                break
        if lw is None:
            lw = min(2048, ((hw + LANES - 1) // LANES) * LANES)
        x = jnp.asarray((rng.randn(*shp) * 0.5).astype(np.float32)) \
            .astype(jnp.bfloat16)
        t_xla = time_fn(xla_stats, x)
        try:
            t_pl = time_fn(lambda v, _lw=lw: pallas_stats(v, _lw), x)
            m1, v1 = jax.jit(xla_stats)(x)
            m2, v2 = jax.jit(lambda v: pallas_stats(v, lw))(x)
            ok = bool(np.allclose(np.asarray(m1), np.asarray(m2),
                                  atol=2e-2) and
                      np.allclose(np.asarray(v1), np.asarray(v2),
                                  atol=2e-2))
        except Exception as e:                     # noqa: BLE001
            t_pl, ok = None, f"{type(e).__name__}: {e}"[:200]
        results[str(shp)] = {
            "xla_us": round(t_xla * 1e6, 1),
            "pallas_us": round(t_pl * 1e6, 1) if t_pl else None,
            "pallas_vs_xla": round(t_xla / t_pl, 2) if t_pl else None,
            "lw": lw, "match": ok,
        }
        total_xla += t_xla
        total_pl += t_pl or t_xla
        print(shp, json.dumps(results[str(shp)]), flush=True)

    print(json.dumps({
        "total_xla_ms_all_bn_shapes": round(total_xla * 1e3, 3),
        "total_pallas_ms_all_bn_shapes": round(total_pl * 1e3, 3),
        "speedup": round(total_xla / total_pl, 2),
        "results": results}))


if __name__ == "__main__":
    main()
