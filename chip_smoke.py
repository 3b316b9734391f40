"""Quickest proof that mxnet_tpu still starts on the chip.

``python chip_smoke.py`` drives the main paths once on ONE attached TPU, at
the full width of the models the repo supports, through the entry points a
user calls, and checks what comes out:

- ``train`` — BERT-base (12 x 768, 12 heads, FFN 3072, vocab 30,522) at
  32 x 128 tokens through ``mxnet_tpu.parallel.SPMDTrainer`` on a one-device
  mesh, then ResNet-50 at batch 32 through ``hybridize()`` +
  ``autograd.record()`` + ``gluon.Trainer.step``;
- ``flash`` — the Pallas flash-attention kernel, compiled not interpreted,
  against its dense reference, and one BERT-base forward that takes it;
- ``serve`` — ``decode_base`` behind the HTTP ``Gateway`` in process;
- ``fleet`` — the same requests through ``Gateway(owner=Supervisor(...))``:
  the front end stays off the device, the owner child holds it.

``--chips 4`` runs only the multi-chip path and what it is compared with;
``--ops`` runs only the operator sweep, chip against CPU.

This process never initialises a JAX backend.  Each phase is a child process
— one holder of the chip at a time — that prints JSON lines, its result
last.  The script refuses to run where JAX finds no TPU.  The last line of
stdout is ``{"ok": true, "device": {"platform": "tpu", "kind": "...",
"count": N}}``; any phase that fails makes it ``"ok": false`` and the exit
code non-zero.  Every input is made from ``--seed``.
"""
import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# Full sizes: the widths are the models' own, only step counts are small.
# tests/test_chip_smoke.py passes the same keys at a tiny size.
FULL = {
    "bert": {"model": "bert_base", "vocab": 30522, "batch": 32, "seq": 128,
             "masked": 20, "steps": 5},
    "resnet": {"model": "resnet50_v1", "classes": 1000, "batch": 32,
               "image": 224, "steps": 3},
    "flash_shapes": [(32, 12, 128, 64), (1, 12, 8192, 64)],
    # the decode session's ladders cut to a handful of programs: a cold run
    # is minutes of compiling, not tens of minutes
    "decode": {"model": "decode_base", "vocab": 512, "max_length": 128,
               "batch_buckets": (1, 4), "seq_buckets": (16,),
               "page_size": 16, "spec_k": 2, "new_tokens": 24},
    "owner_spec": "chip_smoke:build_owner",
    "attention": {"heads": 12, "seq": 2048, "dim": 64},
    "multichip_steps": 3,
}

# Written tolerances.
# bf16 keeps 8 bits of mantissa and attention outputs are O(1): the kernel
# (f32 accumulation over bf16 inputs) against the f32 "highest" reference
# differs by at most a few bf16 ulps of the output.
FLASH_TOL = 2e-2
# The flash and the dense path of one BERT forward round differently at the
# MXU's default precision in each of the layers; LayerNorm keeps activations
# O(1), so a masking or layout fault is O(1) and rounding is well under 0.1.
BERT_PATH_TOL = 1e-1
# dp x tp changes the order of the reductions, not the math: the loss (about
# ln(vocab) at the start) agrees to well under a percent.
MULTICHIP_LOSS_RTOL = 1e-2
# ring/Ulysses run on f32 inputs at the MXU's default precision, which rounds
# q, k and v to bf16 (2^-9 relative) before each of the two matmuls; the
# reference runs at "highest".  Outputs are O(1) to O(3), so rounding stays
# near 1e-2 while a wrong rotation, offset or mask is O(1).
SP_ATTENTION_TOL = 5e-2
# --ops: transcendentals use different polynomial approximations per backend
# (observed deltas ~6e-5); real defects are orders of magnitude larger.
OPS_RTOL, OPS_ATOL = 1e-3, 1e-4


# ---------------------------------------------------------------- helpers
def _device():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _require_on(platform, what, arrays):
    """The devices ``arrays`` live on, as sorted strings; raises unless every
    one of them is a ``platform`` device."""
    devices = {d for a in arrays for d in a.devices()}
    stray = sorted(str(d) for d in devices if d.platform != platform)
    if stray:
        raise AssertionError(f"{what}: expected every array on a "
                             f"{platform!r} device, found {stray}")
    return sorted(str(d) for d in devices)


def _memory(devices=None):
    import jax
    return {str(d): (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in (devices or jax.local_devices()[:1])}


class _Compiles:
    """Counts XLA backend compiles (cache loads included) while open."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __enter__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event, _secs, **_kw):
        self.n += event == self.EVENT

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def _ctx(platform):
    import mxnet_tpu as mx
    return mx.tpu(0) if platform == "tpu" else mx.cpu(0)


def _bert(cfg, seed):
    """An initialised BERT (host parameters) and one fixed seeded batch."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.models import get_bert_model

    mx.random.seed(seed)
    np.random.seed(seed)
    net = get_bert_model(cfg["model"], vocab_size=cfg["vocab"],
                         max_length=cfg["seq"], dropout=0.0)
    net.initialize()
    rng = np.random.RandomState(seed)
    b, s, m, vocab = cfg["batch"], cfg["seq"], cfg["masked"], cfg["vocab"]
    tokens = rng.randint(0, vocab, (b, s)).astype("int32")
    segments = rng.randint(0, 2, (b, s)).astype("int32")
    mask = np.ones((b, s), "float32")
    positions = rng.randint(0, s, (b, m)).astype("int32")
    label = rng.randint(0, vocab, (b, m)).astype("float32")
    # one two-row forward on the host materialises the deferred shapes
    net(*(mx.nd.array(a[:2]) for a in (tokens, segments, mask, positions)))
    return net, (tokens, segments, mask, positions), label


def _bert_trainer(net, vocab, mesh):
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import FunctionalOptimizer, SPMDTrainer

    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()

    def loss_fn(out, lab):
        _seq, _pooled, mlm, _nsp = out
        return ce(mlm.reshape((-1, vocab)), lab.reshape((-1,)))

    return SPMDTrainer(net, loss_fn, FunctionalOptimizer("adam", 1e-4),
                       mesh, n_in=4)


def _train_steps(trainer, data, label, steps):
    """Losses, wall seconds and backend compiles of each of ``steps`` steps
    on one fixed batch; only the first step may compile."""
    losses, seconds, compiles = [], [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        with _Compiles() as c:
            losses.append(float(trainer.step(data, label).asnumpy()))
        seconds.append(round(time.perf_counter() - t0, 3))
        compiles.append(c.n)
    if any(compiles[1:]):
        raise AssertionError(f"train step compiled again after the first "
                             f"step: compiles per step {compiles}")
    return losses, seconds, compiles


def _check_falling(what, losses):
    import math
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"{what}: non-finite loss {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{what}: loss did not fall: {losses}")


# ------------------------------------------------------------ phase: train
def phase_train(size, platform, seed=0):
    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, telemetry
    from mxnet_tpu.parallel import make_mesh

    out = {}
    # --- BERT through SPMDTrainer on a one-device mesh
    cfg = size["bert"]
    net, data, label = _bert(cfg, seed)
    trainer = _bert_trainer(net, cfg["vocab"], make_mesh(n_devices=1))
    losses, seconds, compiles = _train_steps(trainer, data, label,
                                             cfg["steps"])
    _check_falling("bert", losses)
    out["bert"] = {
        "config": cfg, "losses": losses, "step_seconds": seconds,
        "compiles_per_step": compiles,
        "state_on": _require_on(platform, "bert train state",
                                jax.tree_util.tree_leaves(trainer._state)),
        "peak_bytes_in_use": _memory(),
    }
    del trainer, net

    # --- ResNet through the MXNet-native entry: CachedOp + Trainer.step
    cfg = size["resnet"]
    ctx = _ctx(platform)
    mx.random.seed(seed)
    rnet = gluon.model_zoo.vision.get_model(cfg["model"],
                                            classes=cfg["classes"])
    rnet.initialize(ctx=ctx)
    rnet.hybridize()
    sce = gluon.loss.SoftmaxCrossEntropyLoss()
    gtrainer = gluon.Trainer(rnet.collect_params(), "sgd",
                             {"learning_rate": 0.01, "momentum": 0.9})
    rng = np.random.RandomState(seed)
    b, hw = cfg["batch"], cfg["image"]
    x = mx.nd.array(rng.randn(b, 3, hw, hw).astype("float32"), ctx=ctx)
    y = mx.nd.array(rng.randint(0, cfg["classes"], (b,)).astype("float32"),
                    ctx=ctx)
    was_on = telemetry.is_enabled()
    telemetry.enable()
    try:
        losses, seconds, compiles, recompiles = [], [], [], []
        for _ in range(cfg["steps"]):
            t0 = time.perf_counter()
            with _Compiles() as c:
                with autograd.record():
                    loss = sce(rnet(x), y)
                loss.backward()
                gtrainer.step(b)
                losses.append(float(loss.mean().asnumpy()))
            seconds.append(round(time.perf_counter() - t0, 3))
            compiles.append(c.n)
            counters = telemetry.snapshot()["counters"]
            recompiles.append((counters.get("cachedop.recompiles", 0),
                               counters.get("optimizer.compile_miss", 0)))
    finally:
        if not was_on:
            telemetry.disable()
    _check_falling("resnet", losses)
    if recompiles[-1] != recompiles[0] or compiles[-1]:
        raise AssertionError(
            f"resnet: compiling after the first step: (CachedOp recompiles, "
            f"optimizer compile misses) {recompiles}, backend compiles per "
            f"step {compiles}")
    params = list(rnet.collect_params().values())
    out["resnet"] = {
        "config": cfg, "losses": losses, "step_seconds": seconds,
        "recompiles_after_each_step": recompiles,
        "compiles_per_step": compiles,
        "params_on": _require_on(platform, "resnet parameters",
                                 (p.data()._data for p in params)),
        "grads_on": _require_on(platform, "resnet gradients",
                                (p.grad()._data for p in params
                                 if p.grad_req != "null")),
        "loss_on": _require_on(platform, "resnet loss", [loss._data]),
        "process_peak_bytes_in_use": _memory(),     # both models, one process
    }
    return out


# ------------------------------------------------------------ phase: flash
def phase_flash(size, platform, seed=0):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops.pallas_kernels import _reference, flash_attention

    out = {"kernel": []}
    rng = np.random.RandomState(seed)
    for shape in size["flash_shapes"]:
        q, k, v = (jnp.asarray(rng.randn(*shape), jnp.bfloat16)
                   for _ in range(3))
        scale = 1.0 / float(np.sqrt(shape[-1]))
        for causal in (False, True):
            compiled = jax.jit(
                lambda q, k, v, c=causal: flash_attention(q, k, v, c)
            ).lower(q, k, v).compile()
            is_kernel = "tpu_custom_call" in compiled.as_text()
            if is_kernel != (platform == "tpu"):
                raise AssertionError(
                    f"flash {shape} causal={causal}: tpu_custom_call "
                    f"{'present' if is_kernel else 'absent'} on {platform}")
            got = compiled(q, k, v)
            with jax.default_matmul_precision("highest"):
                want = _reference(q, k, v, causal, scale)
            err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                        - want.astype(jnp.float32))))
            if not err <= FLASH_TOL:
                raise AssertionError(f"flash {shape} causal={causal}: max "
                                     f"error {err} > {FLASH_TOL}")
            out["kernel"].append({
                "shape": list(shape), "causal": causal, "dtype": "bfloat16",
                "tpu_custom_call": is_kernel, "max_abs_err": err,
                "on": _require_on(platform, "flash output", [got])})

    # --- BERT forwards: the kernels without and with the key mask, then the
    # same block sent down the dense tail
    cfg = size["bert"]
    net, (tokens, segments, _mask, positions), _ = _bert(cfg, seed)
    ctx = _ctx(platform)
    net.collect_params().reset_ctx(ctx)
    net.hybridize()
    args = [mx.nd.array(a, ctx=ctx) for a in (tokens, segments)]
    pos = mx.nd.array(positions, ctx=ctx)
    ones = mx.nd.ones(tokens.shape, ctx=ctx)
    was_on = telemetry.is_enabled()
    telemetry.enable()
    try:
        calls = lambda: telemetry.snapshot()["counters_by_label"].get(
            "dispatch.op_calls", {}).get('{op="_contrib_flash_attention"}', 0)
        before = calls()
        flash_seq = net(args[0], args[1], None, pos)[0]
        masked_seq = net(args[0], args[1], ones, pos)[0]
        kernel_calls = calls() - before
    finally:
        if not was_on:
            telemetry.disable()
    layers = len(net.encoder.layers._children)
    if kernel_calls != 2 * layers:
        raise AssertionError(
            f"two bert forwards of {layers} layers dispatched "
            f"_contrib_flash_attention {kernel_calls} times")
    for cell in net.encoder.layers._children.values():
        cell.attention._use_flash = False
    net.hybridize()             # trace again, now with the dense tail
    dense_seq = net(args[0], args[1], ones, pos)[0].asnumpy()
    errs = {}
    for name, seq in (("mask_none", flash_seq), ("mask_ones", masked_seq)):
        errs[name] = float(np.max(np.abs(seq.asnumpy() - dense_seq)))
        if not errs[name] <= BERT_PATH_TOL:
            raise AssertionError(f"bert kernels ({name}) vs dense path: max "
                                 f"error {errs[name]} > {BERT_PATH_TOL}")
    out["bert_forward"] = {
        "config": cfg, "flash_attention_dispatches": kernel_calls,
        "shape": list(flash_seq.shape), "max_abs_err_vs_dense_path": errs,
        "on": _require_on(platform, "bert forward", [flash_seq._data])}
    return out


# ------------------------------------------------- phases: serve and fleet
def _decode_session(cfg, seed, ctx=None, aot_cache=None):
    import mxnet_tpu as mx
    from mxnet_tpu.serving.decode import DecodeSession, get_decode_model

    mx.random.seed(seed)
    net = get_decode_model(cfg["model"], vocab_size=cfg["vocab"],
                           max_length=cfg["max_length"])
    net.initialize(ctx=ctx)
    sess = DecodeSession(net, batch_buckets=cfg["batch_buckets"],
                         seq_buckets=cfg["seq_buckets"],
                         page_size=cfg["page_size"], drafter="ngram",
                         spec_k=cfg["spec_k"], aot_cache=aot_cache)
    return net, sess


def _post(port, body, timeout=600):
    import http.client
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/generate", json.dumps(body),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        raw = r.read()
    finally:
        conn.close()
    if r.status != 200:
        raise AssertionError(f"POST /v1/generate -> {r.status}: {raw!r}")
    if not body.get("stream"):
        return json.loads(raw)["token_ids"]
    tokens, done = [], None
    for line in raw.splitlines():
        if not line.startswith(b"data: ") or line == b"data: [DONE]":
            continue
        frame = json.loads(line[len(b"data: "):])
        if "token" in frame:
            tokens.append(frame["token"])
        else:
            done = frame
    if done is None or "error" in done:
        raise AssertionError(f"stream ended with {done}")
    return tokens


def _get_json(port, path):
    import http.client
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, json.loads(r.read())
    finally:
        conn.close()


def _drive_gateway(port, cfg):
    """A few requests through the HTTP door: one alone and buffered, then
    the same one streamed while two others decode beside it.  A request's
    tokens must not depend on its company or on how they are delivered."""
    from concurrent.futures import ThreadPoolExecutor

    def req(prompt, seed, **kw):
        return dict({"model": cfg["model"], "prompt": prompt,
                     "max_new_tokens": cfg["new_tokens"],
                     "temperature": 0.8, "seed": seed}, **kw)

    a = req([5, 9, 2, 7, 1], 7)
    others = [req([3, 3, 8], 11), req([1, 2, 3, 4, 5, 6, 7, 8], 13)]
    solo = _post(port, a)
    with ThreadPoolExecutor(max_workers=3) as pool:
        futs = [pool.submit(_post, port, dict(a, stream=True))] + \
            [pool.submit(_post, port, o) for o in others]
        batched = [f.result() for f in futs]
    if batched[0] != solo:
        raise AssertionError(f"request A alone {solo} != streamed in a "
                             f"batch {batched[0]}")
    if not all(len(t) == cfg["new_tokens"] for t in [solo] + batched):
        raise AssertionError(f"short generations: {[solo] + batched}")
    return {"a_solo_buffered": solo, "a_batched_streamed": batched[0],
            "others": batched[1:]}


def phase_serve(size, platform, seed=0):
    from mxnet_tpu.serving.gateway import Gateway

    cfg = size["decode"]
    t0 = time.perf_counter()
    net, sess = _decode_session(cfg, seed, ctx=_ctx(platform))
    warm_s = round(time.perf_counter() - t0, 3)
    gw = Gateway(name="smoke")
    try:
        gw.add_decode(cfg["model"], sess)
        tokens = _drive_gateway(gw.port, cfg)
        status, health = _get_json(gw.port, "/healthz")
        # the page pools a decode step returned are that step's outputs
        placed = {
            "params_on": _require_on(
                platform, "decode parameters",
                (p.data()._data for p in net.collect_params().values())),
            "kv_pools_on": _require_on(platform, "kv pools after traffic",
                                       sess.cache.pools),
        }
    finally:
        gw.close()
        sess.close()
    stats = sess.stats()
    if stats["pages_in_use"] or stats["slots_in_use"]:
        raise AssertionError(f"leaked KV state after close: {stats}")
    reported = health["devices"].get("gateway:smoke", {}).get(cfg["model"])
    if status != 200 or stats["platform"] != platform or \
            not reported or reported["platform"] != platform:
        raise AssertionError(f"/healthz {status} {health}; stats {stats}")
    return dict(placed, config=cfg, warm_seconds=warm_s, tokens=tokens,
                stats=stats, healthz=health, peak_bytes_in_use=_memory())


def _build_owner(size, aot_cache):
    _net, sess = _decode_session(size["decode"], 0, aot_cache=aot_cache)
    return {"registry": None, "decode": {size["decode"]["model"]: sess}}


def build_owner(aot_cache=None):
    """Builder spec of the fleet phase's device-owner child (full size).
    No ``ctx``: the session's placement rule puts it on the chip."""
    return _build_owner(FULL, aot_cache)


def phase_fleet(size, platform, seed=0, workdir=None):
    """The serve requests through ``Gateway(owner=Supervisor(...))``.  This
    process is the front end and must stay off the device; the owner child
    holds it and reports its own platform back over the RPC."""
    import tempfile
    from mxnet_tpu.serving.fleet import Supervisor
    from mxnet_tpu.serving.gateway import Gateway

    cfg = size["decode"]
    # the AOT program cache at a fixed path under the checkout: a second
    # run loads what the first one compiled.  The socket is a short temp
    # name: a unix socket path may not exceed ~100 bytes.
    aot_dir = os.path.join(workdir or HERE, ".aot_cache", "chip_smoke")
    os.makedirs(aot_dir, exist_ok=True)
    sock_dir = tempfile.mkdtemp(prefix="smoke-")
    t0 = time.perf_counter()
    sup = Supervisor(size["owner_spec"], os.path.join(sock_dir, "o.sock"),
                     aot_cache=aot_dir, ready_timeout_s=900.0)
    try:
        sup.start()
        warm_s = round(time.perf_counter() - t0, 3)
        gw = Gateway(owner=sup, name="smoke-proxy")
        try:
            tokens = _drive_gateway(gw.port, cfg)
            status, health = _get_json(gw.port, "/healthz")
            client = sup.client()
            try:
                owner = client.call("stats", {})
            finally:
                client.close()
        finally:
            gw.close()
    finally:
        sup.stop()
        os.rmdir(sock_dir)
    sess_stats = owner["decode"][cfg["model"]]
    reported = health["devices"].get("gateway:smoke-proxy", {}).get("owner")
    if status != 200 or owner["platform"] != platform or \
            sess_stats["platform"] != platform or \
            not reported or reported["platform"] != platform:
        raise AssertionError(f"owner runs on {owner}; /healthz {health}")
    if owner["pid"] == os.getpid():
        raise AssertionError("the owner is this process")
    if sess_stats["pages_in_use"]:
        raise AssertionError(f"owner leaked KV pages: {sess_stats}")
    return {"config": cfg, "warm_seconds": warm_s, "tokens": tokens,
            "owner": owner, "healthz": health, "frontend_pid": os.getpid(),
            "owner_device": {"platform": owner["platform"],
                             "kind": owner["device_kind"]}}


# -------------------------------------------------------- phase: multichip
def phase_multichip(size, platform, seed=0):
    """BERT on a dp=2 x tp=2 mesh against the same batch on one device, and
    ring/Ulysses attention at sp=4 against the dense reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu import telemetry
    from mxnet_tpu.parallel import (blockwise_attention_reference,
                                    device_mesh, make_mesh,
                                    ring_self_attention,
                                    ulysses_self_attention)

    devices = jax.devices()[:4]
    if len(devices) != 4:
        raise AssertionError(f"need 4 devices, jax has {len(jax.devices())}")
    out = {}
    cfg, steps = size["bert"], size["multichip_steps"]
    # two nets from one seed: a trainer donates its state, and on a mesh
    # that is the parameters' own device that state aliases the block's
    # arrays, so the second trainer gets an identical block of its own
    net, data, label = _bert(cfg, seed)
    one = _bert_trainer(net, cfg["vocab"], make_mesh(n_devices=1))
    ref_losses, ref_seconds, _ = _train_steps(one, data, label, steps)
    del one
    net, data, label = _bert(cfg, seed)
    was_on = telemetry.is_enabled()
    telemetry.enable()
    try:
        # with the bus on, the trainer lists its compiled step's collectives
        # (telemetry.record_collectives) before the first step
        four = _bert_trainer(net, cfg["vocab"],
                             make_mesh(n_devices=4, dp=2, tp=2))
        losses, seconds, _ = _train_steps(four, data, label, steps)
        gauges = telemetry.snapshot()["gauges"]
    finally:
        if not was_on:
            telemetry.disable()
    _check_falling("bert dp2 x tp2", losses)
    np.testing.assert_allclose(losses, ref_losses, rtol=MULTICHIP_LOSS_RTOL)
    collectives = {k: gauges.get(f"trainer.{k}")
                   for k in ("collective_ops", "collective_bytes")}
    if not collectives["collective_ops"]:
        raise AssertionError(f"no collectives in the dp x tp step: {gauges}")

    params = four._state[0]
    tp_sharded = [n for n, a in params.items() if "tp" in str(a.sharding.spec)]
    spread = {n: sorted(str(s.device) for s in params[n].addressable_shards)
              for n in tp_sharded[:3]}
    total = sum(a.nbytes for a in params.values())
    per_device = {str(d): 0 for d in devices}
    for a in params.values():
        for s in a.addressable_shards:
            per_device[str(s.device)] += s.data.nbytes
    if len(tp_sharded) < 10 or \
            any(len(set(v)) != 4 for v in spread.values()):
        raise AssertionError(f"tp did not shard over 4 devices: "
                             f"{len(tp_sharded)} params, {spread}")
    if not all(0 < b < total for b in per_device.values()):
        raise AssertionError(f"per-device parameter bytes {per_device} not "
                             f"below the total {total}")
    out["bert_dp2_tp2"] = {
        "config": cfg, "losses": losses, "one_device_losses": ref_losses,
        "loss_rtol": MULTICHIP_LOSS_RTOL, "step_seconds": seconds,
        "one_device_step_seconds": ref_seconds, "collectives": collectives,
        "tp_sharded_params": len(tp_sharded), "example_shards": spread,
        "param_bytes_total": total, "param_bytes_per_device": per_device,
        "state_on": _require_on(platform, "dp x tp train state",
                                jax.tree_util.tree_leaves(four._state)),
    }
    del four

    # --- sequence parallelism at sp=4
    att = size["attention"]
    mesh = device_mesh({"dp": 1, "sp": 4}, devices=devices)
    rng = np.random.RandomState(seed)
    sharding = NamedSharding(mesh, P("dp", None, "sp", None))
    q, k, v = (jax.device_put(
        jnp.asarray(rng.randn(1, att["heads"], att["seq"], att["dim"]),
                    jnp.float32), sharding) for _ in range(3))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(blockwise_attention_reference(q, k, v, causal=True))
    out["sp4_attention"] = {"config": att, "tol": SP_ATTENTION_TOL}
    for name, fn in (("ring", ring_self_attention),
                     ("ulysses", ulysses_self_attention)):
        got = fn(q, k, v, mesh, causal=True)
        err = float(np.max(np.abs(np.asarray(got) - want)))
        if not err <= SP_ATTENTION_TOL:
            raise AssertionError(f"{name} attention at sp=4: max error "
                                 f"{err} > {SP_ATTENTION_TOL}")
        on = _require_on(platform, f"{name} output", [got])
        if len(on) != 4:
            raise AssertionError(f"{name} output on {on}, expected 4 devices")
        out["sp4_attention"][name] = {"max_abs_err": err, "on": on}
    out["peak_bytes_in_use"] = _memory(devices)
    return out


# -------------------------------------------------------------- phase: ops
def _ops_file(side):
    d = os.path.join(HERE, "chiprun_out")
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"ops_{side}.npz")


def phase_ops(size, platform, seed=0):
    """The registry sweep of tests/chip_consistency_worker.py on this
    process's backend.  The chip child runs first and saves; the CPU child
    runs the same batch and compares."""
    import jax
    import numpy as np
    import mxnet_tpu as mx
    sys.path.insert(0, os.path.join(HERE, "tests"))
    from chip_consistency_sweep import sweep_batch
    from chip_consistency_worker import op_batch

    skips = {}
    with jax.default_matmul_precision("highest"):
        arrays = {k: v.asnumpy()
                  for k, v in op_batch(mx, _ctx(platform)).items()}
        for k, v in sweep_batch(mx, _ctx(platform),
                                collect_skips=skips).items():
            arrays[f"sweep:{k}"] = v.asnumpy()
    failed = {k: v for k, v in skips.items()
              if v not in ("skip-listed", "alias of swept op")
              and not v.startswith(("no ", "needs "))}
    if platform == "tpu":
        np.savez(_ops_file("chip"), **arrays)
        with open(_ops_file("chip") + ".skips.json", "w") as f:
            json.dump(failed, f)
        return {"ops_run": len(arrays), "raised_or_not_synthesised": failed}
    chip = np.load(_ops_file("chip"))
    with open(_ops_file("chip") + ".skips.json") as f:
        chip_failed = json.load(f)
    missing = sorted(set(arrays) - set(chip.files))
    only_chip = sorted(set(chip.files) - set(arrays))
    disagree = {}
    for k in sorted(set(arrays) & set(chip.files)):
        a, b = arrays[k], chip[k]
        if a.shape != b.shape or not np.allclose(
                b, a, rtol=OPS_RTOL, atol=OPS_ATOL, equal_nan=True):
            disagree[k] = (float(np.max(np.abs(
                a.astype("float64") - b.astype("float64"))))
                if a.shape == b.shape else f"shape {b.shape} != {a.shape}")
    report = {
        "ops_on_cpu": len(arrays), "ops_on_chip": len(chip.files),
        "rtol": OPS_RTOL, "atol": OPS_ATOL,
        "no_result_on_chip": {k: chip_failed.get(k.split(":", 1)[-1], "?")
                              for k in missing},
        "only_on_chip": only_chip, "disagree_max_abs_err": disagree,
    }
    # every op the CPU ran must have run on the chip too, and agree
    if missing or only_chip or disagree:
        raise AssertionError(json.dumps(report))
    return report


PHASES = {"train": phase_train, "flash": phase_flash, "serve": phase_serve,
          "fleet": phase_fleet, "multichip": phase_multichip,
          "ops_chip": phase_ops, "ops_cpu": phase_ops}


# ---------------------------------------------------------- child process
def run_child(name, seed):
    """One phase in this process; the result is the last stdout line."""
    from mxnet_tpu.runtime import compile_cache

    platform = "cpu" if name == "ops_cpu" else "tpu"
    t0 = time.perf_counter()
    result = {"phase": name, "ok": False}
    try:
        if name == "fleet":
            # the front end: it never touches a device (the owner child
            # sets up its own compile cache in its main)
            result.update(PHASES[name](FULL, platform, seed))
            result["device"] = result["owner_device"]
        else:
            cache = compile_cache()
            device = _device()
            if device["platform"] != platform:
                print(f"chip_smoke: phase {name!r} needs a {platform!r} "
                      f"device, jax found {device}", file=sys.stderr)
                return 3
            result["device"] = device
            result.update(PHASES[name](FULL, platform, seed))
            result["compile_cache"] = cache.stats()
        result["ok"] = True
    except Exception as e:     # noqa: BLE001 — reported, then exit non-zero
        import traceback
        traceback.print_exc()
        result["error"] = f"{type(e).__name__}: {e}"[:2000]
    result["wall_seconds"] = round(time.perf_counter() - t0, 3)
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


# --------------------------------------------------------- parent process
def _run_phase(name, seed, timeout):
    """Run one phase as a child in its own process group, echo its stdout,
    return (exit code, parsed last line).  The group is killed whatever
    happens: nothing the phase started outlives it."""
    env = dict(os.environ)
    if name == "ops_cpu":
        env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", name,
         "--seed", str(seed)],
        cwd=HERE, env=env, stdout=subprocess.PIPE, text=True,
        start_new_session=True)
    def _kill():
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    # a silent hang prints no line to wake this loop: a timer kills the
    # group, which closes the pipe
    watchdog = threading.Timer(max(timeout, 1.0), _kill)
    watchdog.start()
    last = None
    try:
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            if line.strip():
                last = line
        code = proc.wait()
    finally:
        timed_out = not watchdog.is_alive()
        watchdog.cancel()
        _kill()
        proc.wait()
    if timed_out:
        print(f"chip_smoke: phase {name!r} killed at its time limit",
              file=sys.stderr)
        code = 124
    try:
        result = json.loads(last) if last else None
    except ValueError:
        result = None
    return code, result if isinstance(result, dict) else None


def final_line(ok, device):
    return json.dumps({"ok": bool(ok), "device": device})


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only the multi-chip path and its comparison")
    p.add_argument("--ops", action="store_true",
                   help="only the operator sweep, chip against CPU")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "mxnet_tpu")):
        print("chip_smoke: no mxnet_tpu package beside this script",
              file=sys.stderr)
        return 2
    if args.phase:
        return run_child(args.phase, args.seed)

    if args.chips == 4:
        phases = ["multichip"]
    elif args.ops:
        phases = ["ops_chip", "ops_cpu"]
    else:
        phases = ["train", "flash", "serve", "fleet"]
    t0 = time.monotonic()
    device, results, failed = None, {}, []
    for name in phases:
        code, result = _run_phase(name, args.seed,
                                  timeout=1150 - (time.monotonic() - t0))
        if code == 3 and device is None:
            # no accelerator: refuse, and print no result
            print("chip_smoke: refused, JAX found no TPU", file=sys.stderr)
            return 3
        results[name] = result
        if code != 0 or not result or not result.get("ok"):
            failed.append(name)
            continue
        if name != "ops_cpu":
            found = dict(result["device"])
            found.setdefault("count", device["count"] if device else None)
            if device is not None and found != device:
                failed.append(f"{name}: device {found} != {device}")
            device = device or found
    if not failed and "fleet" in results and \
            results["fleet"]["tokens"] != results["serve"]["tokens"]:
        failed.append("fleet: tokens through the owner differ from the "
                      "in-process gateway's")
    ok = not failed and device is not None and \
        device["platform"] == "tpu" and device["count"] == args.chips
    print(json.dumps({"summary": True, "failed": failed,
                      "wall_seconds": round(time.monotonic() - t0, 1),
                      "phase_seconds": {k: (v or {}).get("wall_seconds")
                                        for k, v in results.items()}}))
    print(final_line(ok, device))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
