"""The KV page format (``serving/decode/kv_format.py``): the one writer and
the one reader of a cache's pools, in each of its formats."""
import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu.serving.decode import PagedKVCache

LAYERS, HEADS, DIM, PAGE, PAGES, ROW_PAGES = 2, 2, 8, 4, 6, 3

# kv_dtype and layout of each case: the three formats over two K/V pools of
# heads, and the raw format over one bfloat16 pool whose row no head divides
# (a latent-attention block's)
_KV = {"layers": LAYERS, "quantizable": True, "shard_heads": HEADS,
       "pools": (("k", HEADS * DIM, "float32"), ("v", HEADS * DIM, "float32"))}
_LATENT = {"layers": LAYERS, "quantizable": False, "shard_heads": None,
           "pools": (("latent", 128, "bfloat16"),)}
FORMATS = {"raw": (None, _KV), "int8": ("int8", _KV),
           "fp8_e4m3": ("fp8_e4m3", _KV), "raw_one_pool": (None, _LATENT)}


@pytest.fixture(params=sorted(FORMATS))
def cache(request):
    kv_dtype, layout = FORMATS[request.param]
    return PagedKVCache(layout=layout, kv_dtype=kv_dtype, page_size=PAGE,
                        num_pages=PAGES, max_pages_per_seq=ROW_PAGES)


def _rows(cache, lead, seed=0, zero=False):
    """One array a value pool, ``lead + row``, as a block hands them to
    ``write`` (a row of heads as ``(heads, head_dim)``)."""
    rng = np.random.RandomState(seed)
    out = []
    for _name, width, dtype in cache.pool_layout:
        row = (cache.num_heads, width // cache.num_heads) \
            if cache.num_heads else (width,)
        x = np.zeros(lead + row) if zero else rng.normal(size=lead + row) * 3
        out.append(jnp.asarray(x, "float32").astype(dtype))
    return tuple(out)


def _error_bound(cache, x):
    """The largest error a stored row may read back with, per row."""
    x = np.asarray(x, "float32")
    x = x.reshape(x.shape[:1] + (-1,))
    if cache.kv_dtype == "int8":      # half a step of (max - min) / 254
        return (x.max(1) - x.min(1)) / 254 * 0.5 * (1 + 1e-5) + 1e-7
    if cache.kv_dtype == "fp8_e4m3":  # three mantissa bits: half of 2**-3
        return np.abs(x).max(1) * 2.0 ** -4
    return np.zeros(x.shape[0])


def _bytes(pools):
    """Each pool's bytes, ``(layers, pages, page_size, bytes a row)``."""
    return [np.asarray(p).view(np.uint8).reshape(p.shape[:3] + (-1,))
            for p in pools]


def test_write_then_read_round_trips(cache):
    """Rows written at (page, offset) come back through a table that names
    their pages: exactly in the raw format, within the format's error in a
    quantized one; and a row written by a (B, S) commit reads back as the
    same row written by a (B,) step."""
    fmt = cache.pages
    tables = jnp.asarray([[2, 4, 0]], "int32")
    pos = np.arange(2 * PAGE)                       # two whole pages
    page = jnp.asarray(np.asarray(tables)[0][pos // PAGE][None])
    off = jnp.asarray((pos % PAGE)[None])
    rows = _rows(cache, (1, pos.size))
    pools = fmt.write(cache.pools, 1, page, off, rows)
    got = fmt.read(pools, 1, tables)
    assert len(got) == len(rows)
    for g, x in zip(got, rows):
        assert g.shape == (1, ROW_PAGES * PAGE) + x.shape[2:]
        g = np.asarray(g, "float32")[0, :pos.size].reshape(pos.size, -1)
        x = np.asarray(x, "float32")[0].reshape(pos.size, -1)
        err = np.abs(g - x).max(1)
        assert (err <= _error_bound(cache, x)).all(), err
        if not fmt.quantized:
            assert (g == x).all()
    # the same rows, one position at a time as a step writes them
    step = cache.pools
    for j in range(pos.size):
        step = fmt.write(step, 1, page[:, j], off[:, j],
                         tuple(x[:, j] for x in rows))
    for a, b in zip(_bytes(step), _bytes(pools)):
        assert (a == b).all()
    # layer 0 was never written
    assert all((np.asarray(g, "float32") == 0).all()
               for g in fmt.read(pools, 0, tables))


def test_zero_row_reads_back_exact_zeros(cache):
    """An all-zero row (a padded position, the trash page) is exactly zero
    after the round trip in every format: no scale of zero divides."""
    fmt = cache.pages
    page, off = jnp.asarray([3, 3], "int32"), jnp.asarray([0, 1], "int32")
    pools = fmt.write(cache.pools, 0, page, off, _rows(cache, (2,), seed=1))
    pools = fmt.write(pools, 0, page[:1], off[:1],
                      _rows(cache, (1,), zero=True))
    for g in fmt.read(pools, 0, jnp.asarray([[3, 0, 0]], "int32")):
        g = np.asarray(g, "float32")[0]
        assert np.isfinite(g).all()
        assert (g[0] == 0).all() and (g[1] != 0).any()
        assert (g[2:] == 0).all()


def test_write_leaves_every_other_page_unchanged(cache):
    """A write to one page changes no byte of any other page of any pool
    (sidecars included), so what a table that does not name it reads is
    unchanged."""
    fmt = cache.pages
    tables = jnp.asarray([[1, 2, 0], [5, 0, 0]], "int32")
    pools = cache.pools
    for layer in range(LAYERS):
        for p in (1, 2, 5):
            pools = fmt.write(
                pools, layer, jnp.full((PAGE,), p, "int32"),
                jnp.arange(PAGE, dtype="int32"),
                _rows(cache, (PAGE,), seed=10 * layer + p))
    before, read_before = _bytes(pools), fmt.read(pools, 1, tables)
    after = fmt.write(pools, 1, jnp.asarray([3], "int32"),
                      jnp.asarray([2], "int32"), _rows(cache, (1,), seed=99))
    changed = False
    for a, b in zip(_bytes(after), before):
        same = a == b
        changed |= not same[1, 3, 2].all()
        same[1, 3, 2] = True
        assert same.all()
    assert changed
    for a, b in zip(fmt.read(after, 1, tables), read_before):
        assert (np.asarray(a, "float32") == np.asarray(b, "float32")).all()


def test_kv_bytes_per_token_is_what_the_pools_hold(cache):
    fmt = cache.pages
    held = sum(int(np.prod(p.shape)) * p.dtype.itemsize for p in cache.pools)
    assert fmt.kv_bytes_per_token * PAGE * PAGES == held
    assert cache.kv_bytes_per_token == fmt.kv_bytes_per_token
    assert len(cache.pools) == len(cache.pool_layout) + fmt.num_sidecars
    assert cache.stats()["kv_dtype"] == fmt.kv_dtype
