"""Native (C++) runtime components, loaded via ctypes.

The reference keeps its IO/runtime layer in C++ behind a flat C ABI
(``include/mxnet/c_api.h``); this package does the same for the TPU-native
rebuild — ``src/io/recordio_reader.cc`` is the first component (RecordIO
framing scan + batched reads, the role of dmlc-core recordio + the chunk
readers in ``src/io/iter_image_recordio_2.cc``).  The library is compiled on
first use with the in-image toolchain (g++; CMakeLists provided for
production builds) and cached next to this file; every entry point has a
pure-Python fallback so the framework works without a compiler.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "..", "..", "src", "io", "recordio_reader.cc")
_SRC_JPEG = os.path.join(_DIR, "..", "..", "src", "io", "jpeg_decode.cc")
_LIB_PATH = os.path.join(_DIR, "libmxnet_tpu_io.so")
_lock = threading.Lock()
_lib = None
_tried = False


# marker recording a failed -ljpeg link (so a reader-only .so is not
# mistaken for up-to-date once libjpeg appears later)
_NOJPEG_MARKER = _LIB_PATH + ".nojpeg"
# hash of the sources the library beside it was built from.  The library is
# ignored by git yet travels with a copied tree, and a copy does not keep
# mtimes: freshness is decided by content, so a stale binary is never loaded
_HASH_PATH = _LIB_PATH + ".src-sha256"


def _src_hash():
    h = hashlib.sha256()
    for path in sorted(glob.glob(os.path.join(os.path.dirname(_SRC),
                                              "*.cc"))):
        h.update(os.path.basename(path).encode() + b"\0")
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _built_from():
    try:
        with open(_HASH_PATH) as f:
            return f.read().strip()
    except OSError:
        return None


def _commit(tmp, src_hash):
    """Install a freshly linked library and record what it was built from
    (the hash goes first: a crash between the two leaves a rebuild, never
    a stale library taken for fresh)."""
    if os.path.exists(_HASH_PATH):
        os.remove(_HASH_PATH)
    os.replace(tmp, _LIB_PATH)
    with open(f"{_HASH_PATH}.tmp.{os.getpid()}", "w") as f:
        f.write(src_hash + "\n")
    os.replace(f.name, _HASH_PATH)


def _build():
    # Link to a temp path and os.replace() over _LIB_PATH: relinking in
    # place would truncate an inode that may still be mapped in-process
    # (the staleness probe dlopens it), risking SIGBUS / a stale mapping.
    tmp = f"{_LIB_PATH}.tmp.{os.getpid()}"
    src_hash = _src_hash()
    # jpeg_decode.cc needs libjpeg; try with it first, fall back to the
    # reader-only library when the dev package is absent (decode then uses
    # the cv2 Python path)
    if os.path.exists(_SRC_JPEG):
        cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
               os.path.abspath(_SRC), os.path.abspath(_SRC_JPEG),
               "-o", tmp, "-ljpeg"]
        try:
            subprocess.run(cmd, check=True, capture_output=True)
            _commit(tmp, src_hash)
            if os.path.exists(_NOJPEG_MARKER):
                os.remove(_NOJPEG_MARKER)
            return
        except subprocess.CalledProcessError:
            with open(_NOJPEG_MARKER, "w") as f:
                f.write("libjpeg link failed; delete this file after "
                        "installing libjpeg to retry\n")
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
           os.path.abspath(_SRC), "-o", tmp]
    subprocess.run(cmd, check=True, capture_output=True)
    _commit(tmp, src_hash)


def load():
    """The ctypes library, building it on first call; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            stale = not os.path.exists(_LIB_PATH) or \
                _built_from() != _src_hash()
            if not stale and os.path.exists(_SRC_JPEG):
                # a reader-only .so from a failed -ljpeg link must retry
                # once the marker is gone (e.g. libjpeg installed later)
                probe = ctypes.CDLL(_LIB_PATH)
                if not hasattr(probe, "jpg_decode_batch") and \
                        not os.path.exists(_NOJPEG_MARKER):
                    stale = True
                handle = probe._handle
                del probe
                import _ctypes
                _ctypes.dlclose(handle)
            if stale:
                _build()
            lib = ctypes.CDLL(_LIB_PATH)
            lib.rio_build_index.restype = ctypes.c_int64
            lib.rio_build_index.argtypes = [
                ctypes.c_char_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_uint64)),
                ctypes.POINTER(ctypes.POINTER(ctypes.c_uint64))]
            lib.rio_free.argtypes = [ctypes.c_void_p]
            lib.rio_read_record.restype = ctypes.c_int64
            lib.rio_read_record.argtypes = [
                ctypes.c_char_p, ctypes.c_uint64,
                ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint64]
            lib.rio_read_batch.restype = ctypes.c_int64
            lib.rio_read_batch.argtypes = [
                ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64),
                ctypes.c_int64, ctypes.POINTER(ctypes.c_uint8),
                ctypes.c_uint64, ctypes.POINTER(ctypes.c_uint64)]
            if hasattr(lib, "jpg_decode_batch"):
                lib.jpg_decode_batch.restype = ctypes.c_int64
                lib.jpg_decode_batch.argtypes = [
                    ctypes.POINTER(ctypes.c_uint8),
                    ctypes.POINTER(ctypes.c_uint64),
                    ctypes.POINTER(ctypes.c_uint64), ctypes.c_int,
                    ctypes.c_int, ctypes.c_int, ctypes.c_int,
                    ctypes.POINTER(ctypes.c_float),
                    ctypes.POINTER(ctypes.c_uint8),
                    ctypes.POINTER(ctypes.c_float),
                    ctypes.POINTER(ctypes.c_float), ctypes.c_float,
                    ctypes.c_int, ctypes.POINTER(ctypes.c_float)]
            if hasattr(lib, "jpg_decode_batch_u8"):
                lib.jpg_decode_batch_u8.restype = ctypes.c_int64
                lib.jpg_decode_batch_u8.argtypes = [
                    ctypes.POINTER(ctypes.c_uint8),
                    ctypes.POINTER(ctypes.c_uint64),
                    ctypes.POINTER(ctypes.c_uint64), ctypes.c_int,
                    ctypes.c_int, ctypes.c_int, ctypes.c_int,
                    ctypes.POINTER(ctypes.c_uint8)]
            _lib = lib
        except Exception:
            _lib = None
        return _lib


def available():
    return load() is not None


def build_index(path):
    """Scan a .rec file → (offsets, lengths) uint64 arrays, or None if the
    native library is unavailable (caller falls back to Python scanning)."""
    lib = load()
    if lib is None:
        return None
    off = ctypes.POINTER(ctypes.c_uint64)()
    lens = ctypes.POINTER(ctypes.c_uint64)()
    n = lib.rio_build_index(path.encode(), ctypes.byref(off),
                            ctypes.byref(lens))
    if n < 0:
        raise IOError(f"native recordio scan failed on {path} (code {n})")
    try:
        offsets = np.ctypeslib.as_array(off, shape=(n,)).copy()
        lengths = np.ctypeslib.as_array(lens, shape=(n,)).copy()
    finally:
        lib.rio_free(off)
        lib.rio_free(lens)
    return offsets, lengths


def read_record(path, offset, length_hint):
    """Read one logical record at ``offset`` → bytes."""
    lib = load()
    if lib is None:
        return None
    cap = max(int(length_hint), 4096)
    buf = (ctypes.c_uint8 * cap)()
    n = lib.rio_read_record(path.encode(), int(offset), buf, cap)
    if n == -4:  # capacity underestimate (multipart longer than hint)
        cap *= 8
        buf = (ctypes.c_uint8 * cap)()
        n = lib.rio_read_record(path.encode(), int(offset), buf, cap)
    if n < 0:
        raise IOError(f"native recordio read failed (code {n})")
    return bytes(bytearray(buf[:n]))


def read_batch(path, offsets, lengths):
    """Read many records in one native call → list[bytes]."""
    lib = load()
    if lib is None:
        return None
    offsets = np.ascontiguousarray(offsets, dtype=np.uint64)
    total = int(np.asarray(lengths, dtype=np.uint64).sum())
    out = np.empty(total, dtype=np.uint8)
    out_lens = np.zeros(len(offsets), dtype=np.uint64)
    n = lib.rio_read_batch(
        path.encode(),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        len(offsets),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        total,
        out_lens.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)))
    if n < 0:
        raise IOError(f"native recordio batch read failed (code {n})")
    recs = []
    pos = 0
    for ln in out_lens:
        ln = int(ln)
        recs.append(out[pos:pos + ln].tobytes())
        pos += ln
    return recs


def decode_available():
    """True when the native library carries the libjpeg decode path."""
    lib = load()
    return lib is not None and hasattr(lib, "jpg_decode_batch")


def _pack_blob(payloads):
    """Concatenate byte payloads into one contiguous (blob, offsets,
    lengths) triple for the batched C entry points."""
    n = len(payloads)
    lengths = np.asarray([len(p) for p in payloads], dtype=np.uint64)
    offsets = np.zeros(n, dtype=np.uint64)
    np.cumsum(lengths[:-1], out=offsets[1:])
    blob = np.empty(int(lengths.sum()), dtype=np.uint8)
    for i, p in enumerate(payloads):
        blob[int(offsets[i]):int(offsets[i]) + len(p)] = \
            np.frombuffer(p, dtype=np.uint8)
    return blob, offsets, lengths


def decode_batch(payloads, out_hw, resize=-1, crop_xy=None, mirror=None,
                 mean=(0.0, 0.0, 0.0), std=(1.0, 1.0, 1.0), scale=1.0,
                 n_threads=4, out=None):
    """Decode+augment a batch of JPEG byte strings into float32 CHW RGB
    (the reference's in-iterator OMP decode, iter_image_recordio_2.cc).

    ``crop_xy``: (n, 2) fractions in [0, 1) for random crops, or None for
    center crop.  ``out``: optional preallocated contiguous float32
    (n, 3, H, W) destination (e.g. a shared-memory ring-slot view) — the
    decoder writes every pixel straight into it, no intermediate batch
    array.  Returns the output array, or None when the native decode path
    is unavailable.
    """
    lib = load()
    if lib is None or not hasattr(lib, "jpg_decode_batch"):
        return None
    n = len(payloads)
    h, w = int(out_hw[0]), int(out_hw[1])
    blob, offsets, lengths = _pack_blob(payloads)
    if crop_xy is None:
        crops = np.full((n, 2), -1.0, dtype=np.float32)
    else:
        crops = np.ascontiguousarray(crop_xy, dtype=np.float32)
    flips = np.zeros(n, dtype=np.uint8) if mirror is None else \
        np.ascontiguousarray(mirror, dtype=np.uint8)
    mean = np.ascontiguousarray(mean, dtype=np.float32)
    std = np.ascontiguousarray(std, dtype=np.float32)
    if out is None:
        out = np.empty((n, 3, h, w), dtype=np.float32)
    elif out.dtype != np.float32 or out.shape != (n, 3, h, w) \
            or not out.flags["C_CONTIGUOUS"]:
        # explicit raise, not assert: this guards a native write into the
        # caller's buffer (python -O must not strip it)
        raise ValueError(
            f"decode_batch out buffer must be contiguous float32 "
            f"{(n, 3, h, w)}, got {out.dtype} {out.shape}")
    rc = lib.jpg_decode_batch(
        blob.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        n, int(resize), h, w,
        crops.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        flips.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        mean.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        std.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        float(scale), int(n_threads),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
    if rc < 0:
        raise IOError(f"native jpeg decode failed on image {-rc - 1}")
    return out


def decode_canvas_available():
    """True when the native library carries the uint8 canvas decoder."""
    lib = load()
    return lib is not None and hasattr(lib, "jpg_decode_batch_u8")


def decode_batch_u8(payloads, out_hw, n_threads=1, out=None):
    """Decode a batch of JPEGs to a fixed uint8 CHW canvas (whole-image
    bilinear resize, no augmentation — that runs as the device prologue).

    ``out``: optional preallocated contiguous uint8 (n, 3, H, W) buffer
    (a shared-memory ring-slot view); allocated when absent.  Returns the
    output array, or None when the native canvas decoder is unavailable.
    """
    lib = load()
    if lib is None or not hasattr(lib, "jpg_decode_batch_u8"):
        return None
    n = len(payloads)
    h, w = int(out_hw[0]), int(out_hw[1])
    blob, offsets, lengths = _pack_blob(payloads)
    if out is None:
        out = np.empty((n, 3, h, w), dtype=np.uint8)
    elif out.dtype != np.uint8 or out.shape != (n, 3, h, w) \
            or not out.flags["C_CONTIGUOUS"]:
        raise ValueError(
            f"decode_batch_u8 out buffer must be contiguous uint8 "
            f"{(n, 3, h, w)}, got {out.dtype} {out.shape}")
    rc = lib.jpg_decode_batch_u8(
        blob.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        n, h, w, int(n_threads),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    if rc < 0:
        raise IOError(f"native jpeg canvas decode failed on image {-rc - 1}")
    return out
