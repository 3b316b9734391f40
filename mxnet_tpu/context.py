"""Device contexts mapped onto JAX devices.

Reference: ``python/mxnet/context.py`` — ``Context(device_type, device_id)``
with ``mx.cpu()``/``mx.gpu()`` constructors and a thread-local default.  In
the TPU-native rebuild, a ``Context`` names a JAX device; ``mx.tpu(i)`` is the
first-class accelerator context and ``mx.gpu(i)`` is accepted as an alias so
that unmodified reference scripts (which say ``mx.gpu(0)``) land on the TPU.
Placement uses ``jax.device_put``; there is no storage manager to build — XLA's
runtime owns HBM (see SURVEY.md §7 translation table, storage row).
"""
from __future__ import annotations

import threading

import jax


class Context:
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}

    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_type, self.device_id = device_type.device_type, device_type.device_id
        else:
            self.device_type = str(device_type)
            self.device_id = int(device_id)
        self._old_ctx = None

    @property
    def device_typeid(self):
        return self.devstr2type[self.device_type]

    # -- JAX device resolution -------------------------------------------------
    def jax_device(self):
        """Resolve to a concrete jax.Device.

        ``gpu``/``tpu`` both resolve to the TPU platform (so reference
        scripts using ``mx.gpu(0)`` run on the chip) and raise where the
        process has none — an accelerator context never lands on the host;
        ``cpu`` resolves to host CPU devices.
        """
        # local_devices: under jax.distributed every process sees the global
        # device list, but may only place data on its own (addressable) ones
        if self.device_type in ("gpu", "tpu"):
            try:
                devs = jax.local_devices(backend="tpu")
            except RuntimeError as e:
                raise RuntimeError(
                    f"{self}: this process has no TPU (jax platform "
                    f"{jax.default_backend()!r}); an accelerator context "
                    f"does not fall back to the host") from e
            if self.device_id >= len(devs):
                raise RuntimeError(
                    f"{self}: only {len(devs)} TPU device(s) attached")
            return devs[self.device_id]
        devs = jax.local_devices(backend="cpu")
        return devs[self.device_id % len(devs)]

    # -- equality / hashing ----------------------------------------------------
    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __str__(self):
        return self.__repr__()

    # -- `with ctx:` scope -----------------------------------------------------
    def __enter__(self):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = Context("cpu", 0)
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        return self

    def __exit__(self, *args):
        Context._default_ctx.value = self._old_ctx

    def empty_cache(self):
        """Reference ``Context.empty_cache`` frees the GPU pool; XLA owns HBM,
        so this is a no-op kept for API compatibility."""


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def gpu(device_id=0):
    """Alias context: reference scripts say ``mx.gpu``; resolves to TPU."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def num_gpus():
    """Number of accelerator chips visible (reference ``mx.context.num_gpus``)."""
    try:
        return len([d for d in jax.devices() if d.platform != "cpu"])
    except RuntimeError:
        return 0


def num_tpus():
    return num_gpus()


def current_context() -> Context:
    if not hasattr(Context._default_ctx, "value"):
        Context._default_ctx.value = Context("cpu", 0)
    return Context._default_ctx.value


def context_from_jax_device(dev) -> Context:
    return Context("cpu" if dev.platform == "cpu" else "tpu", dev.id)
