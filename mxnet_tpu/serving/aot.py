"""Persistent AOT program cache — a fresh process answers its first
request hot.

PR 11 measured the cold-start cliff: merely *building* the decode jits at
``warm()`` deferred XLA compilation to mid-traffic (prefill 46ms -> 3ms
once warm() executes every program).  warm() fixes *when* the compile
happens, but a restarted process still pays the full
trace-every-bucket + XLA-compile bill before its first response.  This
module erases that bill across restarts: every program in the
``compile_for`` / ``compile_grid`` / decode-step ladders is serialized
through ``jax.experimental.serialize_executable`` (the *compiled XLA
executable*, not just the StableHLO — loading skips both the trace and
the compile) into a versioned on-disk cache, and ``warm(aot_cache=...)``
loads instead of compiling.

Because the cache holds the byte-exact executable the cold process ran,
a warm-started process produces **bitwise-identical** outputs — the CI
gateway stage asserts identical token streams across a process restart.

Safety model (an AOT cache must never serve a stale or torn program):

- **Versioned key space.**  Entries live under
  ``<dir>/aot-v1/<backend>-jax<ver>-jaxlib<ver>/<model_key>/``; the
  header repeats backend + jax/jaxlib versions + model key + entry name
  and every field is re-checked at load, so a jaxlib upgrade or a model
  edit can never replay an old binary.
- **crc-checked payloads.**  The pickled executable blob carries a
  crc32; a flipped bit or truncated file fails the check.
- **Atomic commits.**  Entries are written with
  :func:`mxnet_tpu.resilience.durable.replace_file_atomic` (temp +
  fsync + rename + parent-dir fsync) — a crash mid-store leaves the old
  complete entry or none, never a torn one.
- **Fallback, never failure.**  ANY load problem (corrupt, truncated,
  wrong version, unpicklable, undeserializable) counts a
  ``gateway.aot_cache_fallback`` and returns a miss; the caller compiles
  fresh exactly as if the cache were cold.
"""
from __future__ import annotations

import hashlib
import io
import json
import os
import pickle
import struct
import threading
import zlib

from ..resilience import durable as _durable
from ..telemetry import bus as _tel

__all__ = ["ProgramCache", "model_signature", "as_program_cache",
           "AOT_FORMAT"]

_MAGIC = b"MXAOT\x01\n"
AOT_FORMAT = 1


class _RestrictedUnpickler(pickle.Unpickler):
    """The blob is trusted-by-construction (we wrote it), but the crc is
    not an integrity *authenticator* — refuse to resolve anything outside
    the modules the serialized-executable format actually uses, so a
    corrupted-but-crc-patched entry degrades to a fallback, not an
    arbitrary-code load."""

    _ALLOWED_PREFIXES = ("jax", "jaxlib", "numpy", "builtins")

    def find_class(self, module, name):
        if module.split(".", 1)[0] not in self._ALLOWED_PREFIXES:
            raise pickle.UnpicklingError(
                f"aot cache entry references {module}.{name}")
        return super().find_class(module, name)


def _env_fingerprint():
    import jax
    import jaxlib
    return {
        "backend": jax.default_backend(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
    }


def model_signature(block, salt=""):
    """A stable hex key naming *this model as a compile input*: parameter
    names/shapes/dtypes, the block's class, the source of its defining
    module (an edited ``step_program`` must miss), and any caller ``salt``
    (serving geometry — bucket ladders, page/pool shapes — belongs
    there).  Parameter *values* are deliberately excluded: programs are
    functions of shapes, and a weight update must keep hitting."""
    import inspect
    h = hashlib.sha256()
    cls = type(block)
    h.update(f"{cls.__module__}.{cls.__qualname__}".encode())
    try:
        h.update(inspect.getsource(inspect.getmodule(cls)).encode())
    except (OSError, TypeError):
        pass
    try:
        params = sorted(block.collect_params().items())
    except Exception:
        params = []
    # param names are hashed *relative to the block's prefix*: gluon
    # auto-prefixes carry a process-global instance counter
    # (``hybridsequential0_`` vs ``hybridsequential1_``), and the same
    # model re-built in a fresh process must map to the same key
    prefix = getattr(block, "prefix", "") or ""
    for name, p in params:
        if prefix and name.startswith(prefix):
            name = name[len(prefix):]
        h.update(f"{name}:{tuple(p.shape or ())}:{p.dtype}".encode())
    h.update(str(salt).encode())
    return h.hexdigest()[:16]


class ProgramCache:
    """One model's on-disk compiled-program cache.

    Parameters
    ----------
    cache_dir : str
        Root directory (shared across models and environments; the
        versioned subtree is managed here).
    model_key : str
        Output of :func:`model_signature` (or any stable string naming
        the model + geometry).
    fault_site : str
        ``resilience.faults`` site armed inside entry writes
        (``aot.write``) — the mid-store crash drill.
    """

    def __init__(self, cache_dir, model_key, fault_site="aot.write"):
        env = _env_fingerprint()
        self._env = env
        self.model_key = str(model_key)
        self.dir = os.path.join(
            str(cache_dir), f"aot-v{AOT_FORMAT}",
            f"{env['backend']}-jax{env['jax']}-jaxlib{env['jaxlib']}",
            self.model_key)
        self._fault_site = fault_site
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.fallbacks = 0
        self.stores = 0

    # ----------------------------------------------------------------- paths
    def path(self, name):
        safe = "".join(c if c.isalnum() or c in "-_." else "_"
                       for c in str(name))
        return os.path.join(self.dir, f"{safe}.aotp")

    def entries(self):
        """Names of the entries currently on disk (committed files only)."""
        try:
            return sorted(f[:-5] for f in os.listdir(self.dir)
                          if f.endswith(".aotp"))
        except OSError:
            return []

    # ------------------------------------------------------------------ load
    def load(self, name):
        """``(callable, extra_meta)`` for a valid entry, else ``None``.

        Every failure mode — missing, truncated, corrupt, version or
        model mismatch — is a *miss with a reason*, never an exception:
        the caller falls back to a fresh compile and the reason lands on
        the ``gateway.aot_cache_fallback`` counter."""
        path = self.path(name)
        try:
            with open(path, "rb") as f:
                data = f.read()
        except OSError:
            self._miss(name)
            return None
        reason = self._validate_and_load(name, data)
        if isinstance(reason, str):
            self._fallback(name, reason)
            return None
        with self._lock:
            self.hits += 1
        if _tel.enabled:
            _tel.count("gateway.aot_cache_hits", entry=str(name))
        return reason        # (callable, extra)

    def _validate_and_load(self, name, data):
        """Returns ``(callable, extra)`` or a reason string."""
        if not data.startswith(_MAGIC):
            return "bad_magic"
        off = len(_MAGIC)
        if len(data) < off + 4:
            return "truncated"
        (hlen,) = struct.unpack("<I", data[off:off + 4])
        off += 4
        if len(data) < off + hlen:
            return "truncated"
        try:
            header = json.loads(data[off:off + hlen].decode())
        except (UnicodeDecodeError, json.JSONDecodeError):
            return "bad_header"
        off += hlen
        if header.get("format") != AOT_FORMAT:
            return "format_version"
        for k, v in self._env.items():
            if header.get(k) != v:
                return f"env_{k}"
        if header.get("model_key") != self.model_key:
            return "model_key"
        if header.get("name") != str(name):
            return "entry_name"
        blob = data[off:]
        if len(blob) != header.get("payload_len"):
            return "truncated"
        if zlib.crc32(blob) & 0xffffffff != header.get("crc32"):
            return "crc"
        try:
            payload, in_tree, out_tree, extra = \
                _RestrictedUnpickler(io.BytesIO(blob)).load()
        except Exception:
            return "unpickle"
        try:
            import jax
            from jax.experimental import serialize_executable as _se
            # load onto the devices the program was compiled for — the
            # default is every device of the backend, and a one-device
            # program then refuses its arguments on a multi-device host
            ids = header.get("device_ids")
            devices = [d for i in ids or () for d in jax.devices()
                       if d.id == i] or None
            fn = _se.deserialize_and_load(payload, in_tree, out_tree,
                                          execution_devices=devices)
        except Exception:
            return "deserialize"
        return fn, extra

    # ----------------------------------------------------------------- store
    def store(self, name, compiled, extra=None):
        """Serialize a ``jax`` AOT-``Compiled`` stage and commit it
        atomically.  Returns True on success; a failed store warns via
        telemetry and returns False (serving must not die because a cache
        write did)."""
        try:
            from jax.experimental import serialize_executable as _se
            payload, in_tree, out_tree = _se.serialize(compiled)
            blob = pickle.dumps((payload, in_tree, out_tree, extra or {}),
                                protocol=pickle.HIGHEST_PROTOCOL)
            header = dict(self._env)
            import jax
            shardings = jax.tree_util.tree_leaves(compiled.input_shardings)
            header.update(format=AOT_FORMAT, model_key=self.model_key,
                          name=str(name), payload_len=len(blob),
                          crc32=zlib.crc32(blob) & 0xffffffff,
                          device_ids=sorted({d.id for sh in shardings
                                             for d in sh.device_set}))
            hjson = json.dumps(header, sort_keys=True).encode()
            data = _MAGIC + struct.pack("<I", len(hjson)) + hjson + blob
            os.makedirs(self.dir, exist_ok=True)
            _durable.replace_file_atomic(self.path(name), data,
                                         site=self._fault_site)
        except Exception as e:     # noqa: BLE001 — cache writes are advisory
            if _tel.enabled:
                _tel.count("gateway.aot_cache_store_failures")
                _tel.instant("gateway.aot_cache_store_failure",
                             entry=str(name), error=repr(e))
            return False
        with self._lock:
            self.stores += 1
        if _tel.enabled:
            _tel.count("gateway.aot_cache_stores", entry=str(name))
        return True

    def load_or_build(self, name, jit_fn, args, kwargs=None, extra=None):
        """The one call sites use: load ``name``; on any miss, lower +
        compile ``jit_fn`` at the example ``args``/``kwargs``, persist,
        and return the fresh ``Compiled``.

        Returns ``(callable, extra_meta, loaded)`` — ``loaded`` says
        whether the executable came off disk (and therefore cost no
        XLA compile)."""
        hit = self.load(name)
        if hit is not None:
            fn, meta = hit
            return fn, meta, True
        compiled = jit_fn.lower(*args, **(kwargs or {})).compile()
        self.store(name, compiled, extra=extra)
        return compiled, dict(extra or {}), False

    # ------------------------------------------------------------- telemetry
    def _miss(self, name):
        with self._lock:
            self.misses += 1
        if _tel.enabled:
            _tel.count("gateway.aot_cache_misses", entry=str(name))

    def _fallback(self, name, reason):
        with self._lock:
            self.misses += 1
            self.fallbacks += 1
        if _tel.enabled:
            _tel.count("gateway.aot_cache_misses", entry=str(name))
            _tel.count("gateway.aot_cache_fallback", reason=reason)
            _tel.instant("gateway.aot_cache_fallback", entry=str(name),
                         reason=reason)

    def stats(self):
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "fallbacks": self.fallbacks, "stores": self.stores,
                    "dir": self.dir}

    def __repr__(self):
        return (f"ProgramCache({self.dir!r}, hits={self.hits}, "
                f"misses={self.misses}, fallbacks={self.fallbacks})")


def as_program_cache(aot_cache, block, salt=""):
    """Normalize a user-facing ``aot_cache=`` argument: a directory path
    becomes a :class:`ProgramCache` keyed by :func:`model_signature`
    (geometry in ``salt``); a ready cache passes through; None stays
    None."""
    if aot_cache is None or isinstance(aot_cache, ProgramCache):
        return aot_cache
    return ProgramCache(aot_cache, model_signature(block, salt=salt))
