"""Bucketed AOT model runtime — the compiled-shape side of the serving stack.

The reference MXNet's inference story was a bare C-API forward
(``src/c_api/c_predict_api.cc``): one executor bound at one shape, recompile
on anything else.  On TPU that failure mode is worse — ``jax.jit`` silently
retraces per input shape, so a server fed organic traffic (1-item requests,
7-item bursts, ...) compiles forever.  The proven fix from TPU serving
stacks is **bucketed static shapes**: commit to a small ladder of batch
sizes (powers of two up to ``max_batch``), AOT-compile every bucket at load
time through the CachedOp path (``HybridBlock.compile_for``), and pad each
micro-batch up to its bucket so steady state replays warmed executables
only.  Padding wastes a bounded slice of FLOPs (counted:
``serving.padded_items`` vs ``serving.batch_items``); recompiles waste
unbounded seconds (counted too: ``serving.compile_miss`` must stay zero
after warmup).
"""
from __future__ import annotations

import numpy as np

from .. import autograd
from .. import ndarray as nd
from ..gluon.block import io_signature
from ..ndarray import NDArray
from ..telemetry import bus as _tel
from .aot import as_program_cache

__all__ = ["ModelRuntime", "default_buckets", "place_block",
           "device_info"]


def place_block(block):
    """Decide where a runtime runs and put ``block`` there — the serving
    stack's one placement rule; returns the ``jax.Device``.

    A runtime runs on the accelerator its block's parameters are committed
    to.  A block left on the host — ``net.initialize()`` with no ``ctx``
    lands on ``cpu(0)``, the MXNet default — moves to the process's first
    device: the chip where there is one, the CPU in the tests.  Without
    this, a jit over host-committed parameters and uncommitted inputs runs
    on the host while the chip sits idle, and nothing says so."""
    import jax
    from ..context import context_from_jax_device
    params = block.collect_params()
    devices = set()
    for p in params.values():
        if p._data is not None:
            devices |= p.data()._data.devices()
    if len(devices) == 1 and next(iter(devices)).platform != "cpu":
        return next(iter(devices))
    device = jax.local_devices()[0]
    if devices != {device}:
        params.reset_ctx(context_from_jax_device(device))
    return device


def device_info(device):
    """``{"platform", "device_kind"}`` of a ``jax.Device`` — what every
    serving ``stats()`` and the gateway's health route report, so a
    deployment that landed on the host says so."""
    return {"platform": device.platform, "device_kind": device.device_kind}


def default_buckets(max_batch):
    """Power-of-two bucket ladder ``1, 2, 4, ...`` capped at ``max_batch``
    (the cap itself is always a bucket, power of two or not)."""
    max_batch = int(max_batch)
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    ladder, b = [], 1
    while b < max_batch:
        ladder.append(b)
        b *= 2
    ladder.append(max_batch)
    return tuple(ladder)


class ModelRuntime:
    """A hybridized Gluon block (or imported symbol+params) wrapped into a
    fixed set of AOT-compiled batch shapes.

    Parameters
    ----------
    block : HybridBlock
        The model.  Hybridized in place if it is not already.
    item_shapes : tuple
        Shape of ONE request's input, without the batch axis — e.g.
        ``(3, 224, 224)`` — or a tuple of such shapes for multi-input
        models (requests then carry a tuple of arrays).
    dtype : str or tuple of str
        Input dtype(s); a single string applies to every input.
    max_batch : int
        Largest micro-batch (and largest bucket).
    buckets : sequence of int, optional
        Explicit bucket ladder; defaults to :func:`default_buckets`.
        The largest bucket must equal ``max_batch``.
    warm : bool
        AOT-compile every bucket now (default).  Pass ``False`` only to
        warm later via :meth:`warm` — serving unwarmed shapes compiles
        mid-traffic and is counted as ``serving.compile_miss``.
    aot_cache : str or ProgramCache, optional
        Persistent program cache (``serving.aot``): a directory path (a
        :class:`~mxnet_tpu.serving.aot.ProgramCache` is derived from the
        model signature + bucket geometry) or a ready cache.  With a warm
        cache, :meth:`warm` deserializes every bucket's executable off
        disk instead of tracing + XLA-compiling it.
    """

    def __init__(self, block, item_shapes, dtype="float32", max_batch=32,
                 buckets=None, name=None, warm=True, aot_cache=None):
        if not getattr(block, "_active", False):
            block.hybridize()
        self._block = block
        self.device = place_block(block)
        self.name = name or getattr(block, "name", "model")
        self.max_batch = int(max_batch)
        self.buckets = tuple(sorted(set(
            int(b) for b in (buckets if buckets is not None
                             else default_buckets(max_batch)))))
        if self.buckets[0] < 1 or self.buckets[-1] != self.max_batch:
            raise ValueError(
                f"buckets {self.buckets} must be >= 1 and end at "
                f"max_batch={self.max_batch}")
        if item_shapes and isinstance(item_shapes[0], (tuple, list)):
            self._item_shapes = tuple(tuple(int(d) for d in s)
                                      for s in item_shapes)
        else:
            self._item_shapes = (tuple(int(d) for d in item_shapes),)
        if isinstance(dtype, (tuple, list)):
            self._dtypes = tuple(str(d) for d in dtype)
            if len(self._dtypes) != len(self._item_shapes):
                raise ValueError("one dtype per input required")
        else:
            self._dtypes = (str(dtype),) * len(self._item_shapes)
        # signatures known compiled for INFERENCE — the steady-state hot
        # path checks this O(1) set, not the block's full history
        self._compiled_sigs = set()
        # bucket geometry is a compile input: a different ladder must not
        # replay another runtime's programs
        self.aot_cache = as_program_cache(
            aot_cache, block,
            salt=f"runtime:{self.buckets}:{self._item_shapes}"
                 f":{self._dtypes}")
        if warm:
            self.warm()

    @classmethod
    def from_exported(cls, symbol_file, input_names, param_file, item_shapes,
                      ctx=None, **kwargs):
        """Load a model exported by ``HybridBlock.export`` (symbol json +
        params file) and wrap it — the multi-model registry's cold-load
        path."""
        from ..gluon import SymbolBlock
        block = SymbolBlock.imports(symbol_file, input_names, param_file,
                                    ctx=ctx)
        block.hybridize()
        return cls(block, item_shapes, **kwargs)

    @property
    def block(self):
        return self._block

    # ------------------------------------------------------------- warmup
    def warm(self):
        """AOT-compile every bucket (CachedOp path) before taking traffic.

        After this, any micro-batch padded to a bucket replays a compiled
        executable — zero steady-state XLA recompiles."""
        def make_example(b):
            return [nd.array(np.zeros((b,) + shp, dt), ctx=self.device)
                    for shp, dt in zip(self._item_shapes, self._dtypes)]

        with _tel.span("serving.warmup", model=self.name,
                       buckets=len(self.buckets)):
            self._compiled_sigs.update(
                self._block.compile_grid(make_example, self.buckets,
                                         cache=self.aot_cache).values())
        if _tel.enabled:
            _tel.count("serving.warmup_compiles", len(self.buckets),
                       model=self.name)

    # ----------------------------------------------------------- bucketing
    def bucket_for(self, n):
        """Smallest bucket that fits ``n`` items."""
        for b in self.buckets:
            if b >= n:
                return b
        raise ValueError(
            f"batch of {n} exceeds max_batch={self.max_batch}")

    def _normalize(self, payload):
        """One request's payload → tuple of per-input numpy rows, shape- and
        dtype-checked.  Raises ``ValueError``/``TypeError`` synchronously so
        a malformed request fails at submit(), not inside a shared batch."""
        rows = payload if isinstance(payload, (tuple, list)) else (payload,)
        if len(rows) != len(self._item_shapes):
            raise ValueError(
                f"model {self.name!r} takes {len(self._item_shapes)} "
                f"input(s) per request, got {len(rows)}")
        out = []
        for r, shp, dt in zip(rows, self._item_shapes, self._dtypes):
            if isinstance(r, NDArray):
                r = r.asnumpy()
            arr = np.asarray(r, dtype=dt)
            if tuple(arr.shape) != shp:
                raise ValueError(
                    f"request input shape {tuple(arr.shape)} != item shape "
                    f"{shp} for model {self.name!r}")
            out.append(arr)
        return tuple(out)

    # ------------------------------------------------------------ execution
    def run_batch(self, rows_list):
        """Run one micro-batch of normalized requests and split the result.

        ``rows_list`` is a list of ``_normalize`` outputs.  Inputs are
        stacked, padded up to the bucket with zero rows (steady state then
        only ever sees warmed signatures), and the padded tail is sliced
        off every output before the per-request split."""
        n = len(rows_list)
        bucket = self.bucket_for(n)
        ins = []
        for i, (shp, dt) in enumerate(zip(self._item_shapes, self._dtypes)):
            stacked = np.stack([rows[i] for rows in rows_list])
            if bucket > n:
                stacked = np.concatenate(
                    [stacked, np.zeros((bucket - n,) + shp, stacked.dtype)])
            ins.append(nd.array(stacked, dtype=dt, ctx=self.device))
        sig = io_signature(ins)
        miss = sig not in self._compiled_sigs
        if miss and sig in self._block.compiled_signatures(training=False):
            # traced elsewhere (shared block, warm=False runtime) —
            # remember it so the hot path stays an O(1) local hit
            self._compiled_sigs.add(sig)
            miss = False
        if _tel.enabled:
            _tel.count("serving.batch_items", n, model=self.name)
            if bucket > n:
                _tel.count("serving.padded_items", bucket - n,
                           model=self.name)
            _tel.gauge("serving.last_batch_size", n, model=self.name)
            if miss:
                _tel.count("serving.compile_miss", model=self.name)
                _tel.instant("serving.compile_miss", model=self.name,
                             batch=n, bucket=bucket, shapes=str(sig[0]))
        with autograd.pause(train_mode=False):
            out = self._block(*ins)
        if miss:
            self._compiled_sigs.add(sig)   # compiled now; count it once
        outs = list(out) if isinstance(out, (list, tuple)) else [out]
        host = [o.asnumpy()[:n] for o in outs]
        if len(host) == 1:
            return [host[0][i] for i in range(n)]
        return [tuple(h[i] for h in host) for i in range(n)]

    def __call__(self, payload):
        """Synchronous single-request convenience (bypasses batching)."""
        return self.run_batch([self._normalize(payload)])[0]
