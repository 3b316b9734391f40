"""Runtime feature detection (reference ``python/mxnet/runtime.py`` over
``src/libinfo.cc`` — compile-time feature flags surfaced at run time), and
the process's persistent compilation cache."""
from __future__ import annotations

import os

__all__ = ["Features", "Feature", "feature_list", "CompileCache",
           "compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class CompileCache:
    """Where this process keeps JAX's persistent compilation cache, and
    what the cache did since :func:`compile_cache` returned it: ``hits``
    are programs loaded instead of compiled, ``misses`` programs compiled
    and written for the next process."""

    def __init__(self, path):
        self.path = path
        self.hits = 0
        self.misses = 0

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def stats(self):
        return {"dir": self.path, "hits": self.hits, "misses": self.misses}


def compile_cache():
    """Turn on JAX's persistent compilation cache for this process and
    return its :class:`CompileCache`.

    The directory is placed from outside: where ``JAX_COMPILATION_CACHE_DIR``
    is set JAX reads it itself and no directory is set here.  Where it is
    not, the cache lives at ``<checkout>/.jax_cache`` — a fixed path, never
    a temp name, pid or time: the path is part of what a later process must
    repeat to hit.  Every program is kept, however quickly it compiled
    (JAX's default skips those under a second, which is every eager op).
    Call before the first compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cache = CompileCache(path)
    jax.monitoring.register_event_listener(cache._on_event)
    return cache


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return f"{'✔' if self.enabled else '✖'} {self.name}"


def _detect():
    import jax
    feats = {
        "TPU": any(d.platform != "cpu" for d in jax.devices()),
        "XLA": True,
        "CUDA": False, "CUDNN": False, "NCCL": False, "TENSORRT": False,
        "MKLDNN": False, "OPENMP": False, "BLAS_OPEN": False,
        "DIST_KVSTORE": True,   # jax.distributed-backed dist types
        "INT64_TENSOR_SIZE": True,
        "F16C": False,
        "SIGNAL_HANDLER": False,
        "PROFILER": True,
        "OPENCV": _has("cv2"),
        "PALLAS": True,
    }
    return feats


def _has(mod):
    import importlib.util
    return importlib.util.find_spec(mod) is not None


class Features(dict):
    """Mapping name → Feature (reference ``runtime.py:57``)."""

    instance = None

    def __init__(self):
        super().__init__([(k, Feature(k, v)) for k, v in _detect().items()])

    def __repr__(self):
        return str(list(self.values()))

    def is_enabled(self, feature_name):
        feature_name = feature_name.upper()
        if feature_name not in self:
            raise RuntimeError(f"Feature '{feature_name}' is unknown, "
                               f"known features are: {list(self.keys())}")
        return self[feature_name].enabled


def feature_list():
    """List of runtime features (reference ``runtime.py:68``)."""
    return list(Features().values())
