"""Native C++ recordio reader tests — compares against the Python framing
implementation bit-for-bit."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import recordio, _native

pytestmark = pytest.mark.skipif(not _native.available(),
                                reason="native toolchain unavailable")


def _write(tmp_path, n=50):
    frec = str(tmp_path / "n.rec")
    w = recordio.MXRecordIO(frec, "w")
    rng = np.random.RandomState(0)
    payloads = []
    for i in range(n):
        # varied sizes incl. non-multiple-of-4 to exercise padding
        p = rng.bytes(rng.randint(1, 200))
        payloads.append(p)
        w.write(p)
    w.close()
    return frec, payloads


def test_native_index_matches_python(tmp_path):
    frec, payloads = _write(tmp_path)
    offsets, lengths = _native.build_index(frec)
    assert len(offsets) == len(payloads)
    np.testing.assert_array_equal(lengths, [len(p) for p in payloads])
    # Python reader at the native offsets reproduces every payload
    r = recordio.MXRecordIO(frec, "r")
    for off, p in zip(offsets, payloads):
        r.record.seek(int(off))
        assert r.read() == p


def test_native_read_record(tmp_path):
    frec, payloads = _write(tmp_path)
    offsets, lengths = _native.build_index(frec)
    for i in (0, 7, len(payloads) - 1):
        got = _native.read_record(frec, offsets[i], lengths[i])
        assert got == payloads[i]


def test_native_read_batch(tmp_path):
    frec, payloads = _write(tmp_path)
    offsets, lengths = _native.build_index(frec)
    sel = [3, 0, 11, 11, 42]
    recs = _native.read_batch(frec, [offsets[i] for i in sel],
                              [lengths[i] for i in sel])
    for i, r in zip(sel, recs):
        assert r == payloads[i]


def test_image_record_iter_uses_native(tmp_path):
    """ImageRecordIter without .idx goes through the native scanner."""
    fidx, frec = str(tmp_path / "i.idx"), str(tmp_path / "i.rec")
    w = recordio.MXIndexedRecordIO(fidx, frec, "w")
    rng = np.random.RandomState(0)
    for i in range(12):
        img = (rng.rand(20, 20, 3) * 255).astype(np.uint8)
        w.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(i % 3), i, 0), img, img_fmt=".png"))
    w.close()
    it = mx.io.ImageRecordIter(path_imgrec=frec, data_shape=(3, 16, 16),
                               batch_size=4)  # no path_imgidx → scan path
    assert it._lengths is not None  # native index used
    labels = []
    for b in it:
        assert b.data[0].shape == (4, 3, 16, 16)
        labels.extend(b.label[0].asnumpy().tolist())
    assert len(labels) == 12


def test_library_freshness_is_keyed_on_the_source_hash(monkeypatch):
    """The .so is ignored by git yet travels with a copied tree, and a copy
    keeps no mtimes: a library whose recorded source hash differs from
    ``src/io/*.cc`` is rebuilt, never loaded; a matching one is kept however
    old its mtime."""
    import os
    assert _native._built_from() == _native._src_hash()
    builds = []
    monkeypatch.setattr(_native, "_build", lambda: builds.append(1))
    monkeypatch.setattr(_native, "_lib", None)
    monkeypatch.setattr(_native, "_tried", False)
    os.utime(_native._LIB_PATH, (0, 0))           # older than every source
    assert _native.load() is not None and builds == []
    monkeypatch.setattr(_native, "_lib", None)
    monkeypatch.setattr(_native, "_tried", False)
    monkeypatch.setattr(_native, "_built_from", lambda: "another tree's")
    assert _native.load() is not None and builds == [1]
