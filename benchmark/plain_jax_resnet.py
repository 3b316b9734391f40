"""Plain-JAX ResNet-50 v1 AMP train step — the chip ceiling probe.

No framework machinery: raw jnp/lax params-dict model, bf16 compute,
fp32 master weights, SGD+momentum, donated buffers.  Whatever step time
this achieves is the realistic XLA ceiling for the bench headline; the
gap between it and mxnet_tpu's `make_train_step` is framework overhead.
"""
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax


def conv(x, w, stride=1, pad=None):
    kh = w.shape[2]
    if pad is None:
        pad = (kh - 1) // 2
    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ("NCHW", "OIHW", "NCHW"))
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=dn)


def bn(x, p, name, training=True):
    gamma, beta = p[name + "_g"], p[name + "_b"]
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=(0, 2, 3))
    meansq = jnp.mean(x32 * x32, axis=(0, 2, 3))
    var = jnp.maximum(meansq - mean * mean, 0.0)
    inv = lax.rsqrt(var + 1e-5)
    sh = (1, -1, 1, 1)
    out = (x32 - mean.reshape(sh)) * (inv * gamma).reshape(sh) + \
        beta.reshape(sh)
    return out.astype(x.dtype)


def bottleneck(x, p, pre, stride, downsample):
    r = x
    y = conv(x, p[pre + "c1"], stride)
    y = jax.nn.relu(bn(y, p, pre + "bn1"))
    y = conv(y, p[pre + "c2"], 1)
    y = jax.nn.relu(bn(y, p, pre + "bn2"))
    y = conv(y, p[pre + "c3"], 1)
    y = bn(y, p, pre + "bn3")
    if downsample:
        r = bn(conv(x, p[pre + "cd"], stride, pad=0), p, pre + "bnd")
    return jax.nn.relu(y + r)


LAYERS = [3, 4, 6, 3]
CH = [256, 512, 1024, 2048]


def forward(params, x):
    p = {k: v.astype(jnp.bfloat16) for k, v in params.items()
         if v.dtype == jnp.float32}
    x = x.astype(jnp.bfloat16)
    y = conv(x, p["stem"], 2, pad=3)
    y = jax.nn.relu(bn(y, p, "stem_bn"))
    y = lax.reduce_window(y, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    for i, (n, c) in enumerate(zip(LAYERS, CH)):
        for j in range(n):
            stride = 2 if (j == 0 and i > 0) else 1
            y = bottleneck(y, p, f"s{i}_{j}_", stride, j == 0)
    y = jnp.mean(y, axis=(2, 3))
    return y.astype(jnp.float32) @ p["fc_w"].astype(jnp.float32).T + \
        params["fc_b"]


def init_params(rng, classes=1000):
    p = {}

    def w(name, shape):
        p[name] = jnp.asarray(rng.randn(*shape) * 0.05, jnp.float32)

    def bnp(name, c):
        p[name + "_g"] = jnp.ones((c,), jnp.float32)
        p[name + "_b"] = jnp.zeros((c,), jnp.float32)

    w("stem", (64, 3, 7, 7))
    bnp("stem_bn", 64)
    in_c = 64
    for i, (n, c) in enumerate(zip(LAYERS, CH)):
        mid = c // 4
        for j in range(n):
            pre = f"s{i}_{j}_"
            w(pre + "c1", (mid, in_c, 1, 1))
            bnp(pre + "bn1", mid)
            w(pre + "c2", (mid, mid, 3, 3))
            bnp(pre + "bn2", mid)
            w(pre + "c3", (c, mid, 1, 1))
            bnp(pre + "bn3", c)
            if j == 0:
                w(pre + "cd", (c, in_c, 1, 1))
                bnp(pre + "bnd", c)
            in_c = c
    w("fc_w", (classes, 2048))
    p["fc_b"] = jnp.zeros((classes,), jnp.float32)
    return p


def main():
    batch = int(__import__("os").environ.get("PLAIN_BATCH", 32))
    rng = np.random.RandomState(0)
    params = init_params(rng)
    mom = {k: jnp.zeros_like(v) for k, v in params.items()}
    x = jax.device_put(rng.randn(batch, 3, 224, 224).astype("float32"))
    labels = jax.device_put(rng.randint(0, 1000, (batch,)))

    def loss_fn(params, x, labels):
        logits = forward(params, x)
        lse = jax.nn.logsumexp(logits, axis=-1)
        nll = lse - jnp.take_along_axis(logits, labels[:, None],
                                        axis=-1)[:, 0]
        return jnp.mean(nll)

    def step(params, mom, x, labels):
        loss, g = jax.value_and_grad(loss_fn)(params, x, labels)
        new_mom = {k: 0.9 * mom[k] + g[k] for k in params}
        new_p = {k: params[k] - 1e-4 * new_mom[k] for k in params}
        return new_p, new_mom, loss

    step_jit = jax.jit(step, donate_argnums=(0, 1))
    compiled = step_jit.lower(params, mom, x, labels).compile()
    for _ in range(5):
        params, mom, loss = compiled(params, mom, x, labels)
    print("warm loss:", float(np.asarray(loss)))

    # honest timing: value-fetch barrier, RTT subtracted
    probes = [jax.jit(lambda v, i=i: v + i)(jnp.float32(1)) for i in range(6)]
    float(np.asarray(probes[0]))
    rtt = min(_t(lambda p=p: float(np.asarray(p))) for p in probes[1:])
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(20):
            params, mom, loss = compiled(params, mom, x, labels)
        float(np.asarray(loss))
        times.append(time.perf_counter() - t0 - rtt)
    per_step = min(times) / 20
    print(f"plain-JAX resnet50 AMP train: {per_step*1e3:.3f} ms/step, "
          f"{batch/per_step:.1f} img/s, "
          f"MFU={3*4.11e9*batch/per_step/197e12:.3f} (rtt={rtt*1e3:.1f}ms)")


def _t(f):
    t0 = time.perf_counter()
    f()
    return time.perf_counter() - t0


if __name__ == "__main__":
    sys.exit(main())
