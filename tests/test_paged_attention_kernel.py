"""The decode step's paged-attention kernels (``ops.pallas_kernels.
paged_attention`` over K and V pools, ``paged_latent_attention`` over one
pool that is both) under the Pallas interpreter, held to what they replace
on the chip, ``PageFormat.read`` + the block's own attention over the
gathered context (the CPU's form of ``PageFormat.attend``), and to what they
may read: the live rows' LIVE pages and nothing else.  What the chip's
compiler makes of them is ``tests/test_chip_compile.py``'s; how fast they
are, ``PERF.md``'s."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops.pallas_kernels import (paged_attention,
                                          paged_latent_attention)
from mxnet_tpu.serving.decode import (HybridSSMMoELM, LatentMoELM,
                                      WindowMoELM)
from mxnet_tpu.serving.decode.kv_format import PageFormat
from mxnet_tpu.test_utils import counted

PAGE, LAYERS = 16, 2
# the step's tolerance of tests/test_window_moe_lm.py and
# tests/test_hybrid_moe_lm.py, against the largest value expected
TOL = {"float32": 5e-5, "bfloat16": 5e-2}

# (a row's queries, the pools' row widths, a row's output, pages a row):
# MiMo-V2.5's global layers and Nemotron-3-Nano's grouped-query layers (K/V
# heads, query heads a K/V head, key | value width) and A.X-K1's latent
# layers (64 heads over ONE 640-wide row: 512 latent, 64 rotary, 64 of
# padding) as their cells reserve them
SHAPES = {"mimo": ((4, 16, 192), (768, 512), (4, 16, 128), 288),
          "nemotron": ((2, 16, 128), (256, 256), (2, 16, 128), 96),
          "axk1": ((64, 640), (640,), (64, 640), 128)}


@functools.lru_cache(maxsize=None)
def _block(shape, dtype):
    """``(page format, plain(q) -> the block's attention over a gathered
    context, the kernel, what the door is told besides)`` of a block with
    the shape's attention at its published widths; everything else of the
    block is tiny and none of it is read."""
    if shape == "axk1":
        net = LatentMoELM(
            vocab_size=16, hidden_size=16, num_layers=LAYERS, num_heads=64,
            q_lora_rank=16, kv_lora_rank=512, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, intermediate_size=16,
            moe_intermediate_size=16, n_routed_experts=2, n_group=1,
            topk_group=1, num_experts_per_tok=1, dtype=dtype,
            rope_scaling={"factor": 32, "beta_fast": 32, "beta_slow": 1,
                          "original_max_position_embeddings": 4096,
                          "mscale": 1, "mscale_all_dim": 1})
        assert abs(net._scale - 0.1309) < 1e-4      # YaRN's, not 192 ** -0.5
        told = {"scale": net._scale}
        return (PageFormat(net.cache_layout(), page_size=PAGE),
                lambda q: functools.partial(net.context_absorbed, q),
                functools.partial(paged_latent_attention, **told), told)
    (g, r, dk), _widths, (_g, _r, dv), _pages = SHAPES[shape]
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
    return PageFormat(layout, page_size=PAGE), plain, paged_attention, {}


def _edges(pages):
    """Positions at the first token, a page's edge (rows of 15, 16 and 17
    tokens), the default block's edge (32 pages) and the last reserved
    token."""
    return [0, PAGE - 2, PAGE - 1, PAGE, 32 * PAGE - 1, 32 * PAGE,
            pages * PAGE - 1]


def _batches(pages):
    """``{name: positions, -1 a padded row}``: one row at each edge; 32 rows
    with padded rows in front of, between and behind the live ones, the
    edges and some lengths between among them."""
    one = {f"b1-at{p}": [p] for p in _edges(pages)}
    rng = np.random.default_rng(pages)
    mixed = rng.integers(1, pages * PAGE // 3, 32)
    mixed[3:10] = _edges(pages)
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
    ("mimo", "b1-at4607", 1, "bfloat16", 64),
    ("axk1", "b32-mixed", 0, "float32", None),
    ("axk1", "b32-mixed", 0, "bfloat16", 8),
    ("axk1", "b1-at2047", 1, "bfloat16", 32)]


def _inputs(shape, name, dtype):
    """Pools whose every page is NaN but the pages that hold a token of a
    live row; tables that name a NaN page wherever a row holds no token yet
    (reserved, not live) and the trash page 0 for a padded row; the same
    pools with zeros for NaN, for the gathering form to read."""
    per_row, widths, _out, pages = SHAPES[shape]
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
    pools = [jax.random.normal(k, (LAYERS, num_pages, PAGE, w)).astype(dtype)
             for k, w in zip(keys, widths)]
    nans = [jnp.where(lives[None, :, None, None], p, jnp.nan) for p in pools]
    zeros = [jnp.where(lives[None, :, None, None], p, 0) for p in pools]
    q = jax.random.normal(keys[2], (len(positions),) + per_row)
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
    pages, plain, kernel, told = _block(shape, dtype)
    before = [np.asarray(p.astype(jnp.float32)) for p in nans]
    got = kernel(q, *nans, layer, tables, positions,
                 block_pages=block_pages, interpret=True)
    assert got.shape == (len(live),) + SHAPES[shape][2] and \
        got.dtype == jnp.float32
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
                                   plain(q), **told)).reshape(len(live), -1)
    assert np.abs(got[live] - want[live]).max() <= \
        TOL[dtype] * np.abs(want[live]).max()


@pytest.mark.parametrize("shape", ["nemotron", "axk1"])
def test_layer_is_an_operand_not_the_kernels_text(shape):
    """One traced function serves every layer: the layer is a scalar the
    kernel prefetches, so the paged layers of every step program lower one
    body."""
    q, nans, _zeros, tables, positions, _live = _inputs(
        shape, "b32-mixed", "bfloat16")
    kernel = _block(shape, "bfloat16")[2]

    @jax.jit
    def f(layer):
        return kernel(q, *nans, layer, tables, positions, interpret=True)

    outs = [np.asarray(f(jnp.int32(layer))) for layer in range(LAYERS)]
    assert f._cache_size() == 1
    assert np.abs(outs[0] - outs[1]).max() > 0.1
    np.testing.assert_array_equal(outs[1], np.asarray(kernel(
        q, *nans, 1, tables, positions, interpret=True)))


def test_latent_rows_attend_the_token_the_step_just_wrote():
    """The row at a sequence's own position is among what it attends,
    wherever in a page or a block it lies: written along the query of head
    0 and long, it takes all of that head's softmax, and the head's context
    is that row."""
    q, _nans, (pool,), tables, positions, live = _inputs(
        "axk1", "b32-mixed", "bfloat16")
    kernel = _block("axk1", "bfloat16")[2]
    wrote = 30 * q[:, 0] / jnp.linalg.norm(q[:, 0], axis=-1, keepdims=True)
    at = jnp.take_along_axis(tables, (positions // PAGE)[:, None], 1)[:, 0]
    pool = pool.at[1, jnp.where(live, at, 0), positions % PAGE].set(
        wrote.astype(pool.dtype))
    got = np.asarray(kernel(q, pool, 1, tables, positions, interpret=True))
    assert np.abs(got[live, 0] - np.asarray(wrote)[live]).max() < 0.5


def test_the_door_counts_what_is_lowered_and_takes_raw_pools_only():
    """``PageFormat.attend`` lowered for the CPU is the gathering form and
    counts ``decode.attn.paged.lowered{kind="plain"}`` once a call (for the
    chip it is the kernel: ``tests/test_chip_compile.py``), over K and V
    pools and over a latent block's one pool alike: what the format holds
    chooses the door, and the softmax scale goes with the choice.
    Quantized pools, and a layout that is neither, have no such door and
    are refused in words."""
    for shape in ("nemotron", "axk1"):
        q, _nans, zeros, tables, positions, _live = _inputs(
            shape, "b1-at16", "bfloat16")
        pages, plain, _kernel, told = _block(shape, "bfloat16")
        fn = jax.jit(lambda q, *pools: pages.attend(
            pools, 0, tables, positions, q, plain(q), **told))
        assert counted("decode.attn.paged.lowered",
                       lambda: fn.lower(q, *zeros)) == \
            {'{kind="plain",rows="1"}': 1}
        with pytest.raises(ValueError, match="softmax scale"):
            pages.attend(tuple(zeros), 0, tables, positions, q, plain(q),
                         **({} if told else {"scale": 0.1}))
    kv = (("k", 128, "float32"), ("v", 128, "float32"))
    for pools, kv_dtype in ((kv, "int8"), (kv + (("w", 128, "float32"),),
                                           None)):
        layout = {"layers": 1, "pools": pools, "quantizable": True,
                  "shard_heads": 1}
        with pytest.raises(ValueError, match="raw K and V pools or one raw "
                                             "pool that is both"):
            PageFormat(layout, kv_dtype, PAGE).attend((), 0, tables,
                                                      positions, q, None)
