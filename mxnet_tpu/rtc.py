"""Runtime kernel compilation (reference ``python/mxnet/rtc.py`` — NVRTC
CUDA kernels via ``src/common/rtc.cc``).

TPU-native replacement: user-supplied accelerator kernels are **Pallas**
functions, not CUDA source strings — see ``mxnet_tpu/ops/pallas_kernels.py``
for the resident examples and ``CudaModule`` below for the compatibility
story.  ``compile_pallas`` offers the same "hand me source, get a callable"
workflow for Pallas kernel bodies.
"""
from __future__ import annotations

__all__ = ["CudaModule", "CudaKernel", "compile_pallas"]

_MSG = ("CUDA runtime compilation has no TPU equivalent: write the kernel "
        "as a Pallas function instead (jax.experimental.pallas; see "
        "mxnet_tpu/ops/pallas_kernels.py and "
        "/opt/skills/guides/pallas_guide.md). mx.rtc.compile_pallas() "
        "compiles Pallas kernel source for you.")


class CudaModule:
    """Reference ``rtc.py:CudaModule``; raises with migration guidance."""

    def __init__(self, source, options=(), exports=()):
        raise NotImplementedError(_MSG)


class CudaKernel:
    def __init__(self, *a, **kw):
        raise NotImplementedError(_MSG)


def compile_pallas(source, kernel_name, out_shape):
    """Compile Pallas kernel source text into a jitted callable.

    ``source`` must define ``def <kernel_name>(in_ref, ..., out_ref):``
    operating on pl.Ref blocks. Returns ``fn(*arrays) -> array``.
    """
    import jax
    from jax.experimental import pallas as pl

    namespace = {}
    exec(compile(source, "<mx.rtc>", "exec"),
         {"pl": pl, "jnp": __import__("jax.numpy", fromlist=["numpy"]),
          "jax": jax}, namespace)
    kernel = namespace[kernel_name]

    @jax.jit
    def fn(*arrays):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(out_shape[0], out_shape[1]),
            interpret=jax.default_backend() == "cpu",
        )(*arrays)

    return fn
