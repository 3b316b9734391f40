"""The decode step's paged-attention kernel (``ops.pallas_kernels.
paged_attention``) under the Pallas interpreter, held to what it replaces on
the chip, ``PageFormat.read`` + the block's own ``attend`` over the gathered
context (the CPU's form of ``PageFormat.attend``), and to what it may read:
the live rows' LIVE pages and nothing else.  What the chip's compiler makes
of it is ``tests/test_chip_compile.py``'s; how fast it is, ``PERF.md``'s."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops.pallas_kernels import paged_attention
from mxnet_tpu.serving.decode import HybridSSMMoELM, WindowMoELM
from mxnet_tpu.serving.decode.kv_format import PageFormat
from mxnet_tpu.test_utils import counted

PAGE, LAYERS = 16, 2
# the step's tolerance of tests/test_window_moe_lm.py and
# tests/test_hybrid_moe_lm.py, against the largest value expected
TOL = {"float32": 5e-5, "bfloat16": 5e-2}

# (K/V heads, query heads a K/V head, key width, value width, pages a row):
# MiMo-V2.5's global layers and Nemotron-3-Nano's grouped-query layers as
# their cells reserve them
SHAPES = {"mimo": (4, 16, 192, 128, 288), "nemotron": (2, 16, 128, 128, 96)}


@functools.lru_cache(maxsize=None)
def _block(shape, dtype):
    """``(page format, plain(q) -> the block's attention over a gathered
    context)`` of a block with the shape's attention at its published
    widths; everything else of the block is tiny and none of it is read."""
    g, r, dk, dv, _pages = SHAPES[shape]
    if shape == "mimo":
        net = WindowMoELM(
            vocab_size=16, hidden_size=16, layer_pattern=(0, 1),
            moe_layer_freq=(0, 1), num_attention_heads=g * r,
            num_key_value_heads=g, swa_num_key_value_heads=g, head_dim=dk,
            v_head_dim=dv, sliding_window=8, intermediate_size=16,
            moe_intermediate_size=16, n_routed_experts=2,
            num_experts_per_tok=1, dtype=dtype)
        plain = lambda q: lambda k, v, mask: net.attend(
            {}, 0, q[:, None], k, v, mask)[:, 0]
    else:
        net = HybridSSMMoELM(
            vocab_size=16, hidden_size=16, pattern="M*E", mamba_num_heads=2,
            mamba_head_dim=4, ssm_state_size=8, n_groups=1,
            num_attention_heads=g * r, num_key_value_heads=g, head_dim=dk,
            moe_intermediate_size=16, moe_shared_expert_intermediate_size=16,
            n_routed_experts=2, num_experts_per_tok=1, dtype=dtype)
        plain = lambda q: lambda k, v, mask: net.attend_heads(
            q[:, None], k, v, mask)[:, 0]
    layout = dict(net.cache_layout(), layers=LAYERS)
    layout.pop("state")
    return PageFormat(layout, page_size=PAGE), plain


def _edges(pages):
    """Positions at the first token, a page's edge, the default block's edge
    (32 pages) and the last reserved token."""
    return [0, PAGE - 1, PAGE, 32 * PAGE - 1, 32 * PAGE, pages * PAGE - 1]


def _batches(pages):
    """``{name: positions, -1 a padded row}``: one row at each edge; 32 rows
    with padded rows in front of, between and behind the live ones, the
    edges and some lengths between among them."""
    one = {f"b1-at{p}": [p] for p in _edges(pages)}
    rng = np.random.default_rng(pages)
    mixed = rng.integers(1, pages * PAGE // 3, 32)
    mixed[3:9] = _edges(pages)
    for padded in (slice(0, 3), slice(12, 15), slice(22, 32)):
        mixed[padded] = -1
    return dict(one, **{"b32-mixed": mixed.tolist(),
                        "b32-padded": [-1] * 32})


CASES = [(shape, name, layer, dtype, block_pages)
         for shape, (*_w, pages) in SHAPES.items()
         for name in _batches(pages)
         for layer, dtype, block_pages in [(1, "bfloat16", None)]] + [
    # the blocks as a float32 session builds them; blocks of other sizes
    # (every page its own block: the two buffers alternate 20 times and more)
    ("mimo", "b32-mixed", 0, "float32", None),
    ("nemotron", "b32-mixed", 1, "float32", None),
    ("mimo", "b32-mixed", 0, "bfloat16", 1),
    ("nemotron", "b32-mixed", 0, "bfloat16", 8),
    ("mimo", "b1-at4607", 1, "bfloat16", 64)]


def _inputs(shape, name, dtype):
    """Pools whose every page is NaN but the pages that hold a token of a
    live row; tables that name a NaN page wherever a row holds no token yet
    (reserved, not live) and the trash page 0 for a padded row; the same
    pools with zeros for NaN, for the gathering form to read."""
    g, r, dk, dv, pages = SHAPES[shape]
    positions = np.asarray(_batches(pages)[name], np.int32)
    live = positions >= 0
    held = np.where(live, positions // PAGE + 1, 0)
    num_pages = 1 + int(held.sum()) + 7
    rng = np.random.default_rng(len(positions) + pages)
    ids = rng.permutation(np.arange(1, num_pages))
    tables = np.zeros((len(positions), pages), np.int32)
    lives, at = np.zeros(num_pages, bool), 0
    for i, n in enumerate(held):
        if live[i]:
            tables[i, :n] = ids[at:at + n]
            tables[i, n:] = ids[-1 - rng.integers(0, 7, pages - n)]
            lives[ids[at:at + n]] = True
            at += n
    keys = jax.random.split(jax.random.PRNGKey(at), 3)
    pools = [jax.random.normal(k, (LAYERS, num_pages, PAGE, g * w)
                               ).astype(dtype)
             for k, w in zip(keys, (dk, dv))]
    nans = [jnp.where(lives[None, :, None, None], p, jnp.nan) for p in pools]
    zeros = [jnp.where(lives[None, :, None, None], p, 0) for p in pools]
    q = jax.random.normal(keys[2], (len(positions), g, r, dk))
    return (q, nans, zeros, jnp.asarray(tables),
            jnp.asarray(np.maximum(positions, 0)), live)


@pytest.mark.parametrize("shape,name,layer,dtype,block_pages", CASES)
def test_live_pages_are_read_where_they_lie_and_nothing_else(
        shape, name, layer, dtype, block_pages):
    """The kernel's output for every live row is the block's attention over
    the row's gathered context, within the step's tolerance, with every
    page that holds no token of a live row NaN: a page past a row's
    position, a page no table names and the trash page are not read, not
    merely masked.  A padded row, wherever it stands, gives finite zeros.
    The pools keep their bits."""
    q, nans, zeros, tables, positions, live = _inputs(shape, name, dtype)
    pages, plain = _block(shape, dtype)
    before = [np.asarray(p.astype(jnp.float32)) for p in nans]
    got = paged_attention(q, *nans, layer, tables, positions,
                          block_pages=block_pages, interpret=True)
    g, r, _dk, dv, _pages = SHAPES[shape]
    assert got.shape == (len(live), g, r, dv) and got.dtype == jnp.float32
    got = np.asarray(got).reshape(len(live), -1)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[~live], 0.0)
    for pool, was in zip(nans, before):
        np.testing.assert_array_equal(np.asarray(pool.astype(jnp.float32)),
                                      was)
    if not live.any():
        return
    # on the CPU the door is the gathering form: read + the block's attend
    want = np.asarray(pages.attend(tuple(zeros), layer, tables, positions, q,
                                   plain(q)))
    assert np.abs(got[live] - want[live]).max() <= \
        TOL[dtype] * np.abs(want[live]).max()


def test_layer_is_an_operand_not_the_kernels_text():
    """One traced function serves every layer: the layer is a scalar the
    kernel prefetches, so the global layers of every step program lower one
    body."""
    q, nans, _zeros, tables, positions, _live = _inputs(
        "nemotron", "b32-mixed", "bfloat16")

    @jax.jit
    def f(layer):
        return paged_attention(q, *nans, layer, tables, positions,
                               interpret=True)

    outs = [np.asarray(f(jnp.int32(layer))) for layer in range(LAYERS)]
    assert f._cache_size() == 1
    assert np.abs(outs[0] - outs[1]).max() > 0.1
    np.testing.assert_array_equal(outs[1], np.asarray(paged_attention(
        q, *nans, 1, tables, positions, interpret=True)))


def test_the_door_counts_what_is_lowered_and_takes_raw_kv_pools_only():
    """``PageFormat.attend`` lowered for the CPU is the gathering form and
    counts ``decode.attn.paged.lowered{kind="plain"}`` once a call (for the
    chip it is the kernel: ``tests/test_chip_compile.py``); quantized pools
    and a latent block's one pool have no such door."""
    q, _nans, zeros, tables, positions, _live = _inputs(
        "nemotron", "b1-at16", "bfloat16")
    pages, plain = _block("nemotron", "bfloat16")
    fn = jax.jit(lambda q, k, v: pages.attend((k, v), 0, tables, positions,
                                              q, plain(q)))
    assert counted("decode.attn.paged.lowered",
                   lambda: fn.lower(q, *zeros)) == \
        {'{kind="plain",rows="1"}': 1}
    layout = {"layers": 1, "pools": (("k", 128, "float32"),
                                     ("v", 128, "float32")),
              "quantizable": True, "shard_heads": 1}
    with pytest.raises(ValueError, match="raw K and V pools"):
        PageFormat(layout, "int8", PAGE).attend((), 0, tables, positions, q,
                                                None)
    latent = {"layers": 1, "pools": (("latent", 128, "bfloat16"),),
              "quantizable": False, "shard_heads": None}
    with pytest.raises(ValueError, match="raw K and V pools"):
        PageFormat(latent, None, PAGE).attend((), 0, tables, positions, q,
                                              None)
