"""The EVA decode step's attention kernel (``ops.pallas_kernels.
eva_attention``: a slot's ring up to ``position mod window`` and the row's
closed-window summary pages under one softmax) under the Pallas interpreter,
held to its definition, ``PageFormat.attend_window`` lowered for the CPU
(``state.read`` + ``read`` + ``EvaLM.attend_row``: what tier-1 and the plain
reference path run), and to what it may read: the live entries of a live
row's ring, the rows of its closed windows, and nothing else.  Tiny sizes
(windows of 32, chunks of 4, 4 heads of 128, 2 layers, pages of 4 rows: 8
summary rows a window, 2 pages).  What the chip's compiler makes of it is
``tests/test_chip_compile.py``'s; how fast it is, ``PERF.md``'s.

Tolerances as ``tests/test_eva_lm.py`` writes them for the stored dtype,
against the largest value expected: float32 pools multiply at the highest
precision and differ from the definition by summation order alone; bfloat16
pools take the query and the probabilities as bfloat16 too."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops.pallas_kernels import eva_attention
from mxnet_tpu.serving.decode import EvaLM
from mxnet_tpu.serving.decode.kv_format import PageFormat
from mxnet_tpu.test_utils import counted

W, C, HEADS, WIDTH, LAYERS, PAGE = 32, 4, 4, 128, 2, 4
PER_WINDOW = W // C                 # summary rows a closed window
ROW_PAGES, SLOTS = 8, 4             # four windows' summaries a row
TOL = {"float32": 1e-5, "bfloat16": 5e-2}


@functools.lru_cache(maxsize=None)
def _block(dtype):
    net = EvaLM(vocab_size=16, hidden_size=HEADS * WIDTH, num_layers=LAYERS,
                num_attention_heads=HEADS, intermediate_size=16,
                window_size=W, chunk_size=C, num_pred_heads=1,
                max_length=ROW_PAGES * PAGE * C, dtype=dtype)
    return net, PageFormat(net.cache_layout(), page_size=PAGE)


# name -> (positions, -1 a padded row; layer; dtype; pages a block)
CASES = {
    # no summary column: the ring alone, one block and four
    "first-window": ([5], 0, "float32", None),
    "first-window-blocks": ([21], 0, "bfloat16", 2),
    # t mod W = 0 just after a window closed: ONE live ring entry (what the
    # closed window left in the others is masked) and all its summaries
    "window-just-closed": ([W], 0, "float32", None),
    "two-windows-just-closed": ([2 * W], 1, "bfloat16", 1),
    # t mod W = W - 1: every entry of the ring, and the last of its blocks
    "window-end": ([W - 1], 0, "float32", 2),
    "third-window-end": ([3 * W - 1], 1, "bfloat16", 2),
    # a row in its third window beside a row in its first in one batch
    "third-beside-first": ([2 * W + 7, 3], 1, "float32", None),
    "third-beside-first-blocks": ([2 * W + 7, 3], 0, "bfloat16", 1),
    # padded rows in front of, between and behind the live ones
    "padded-rows": ([-1, 3 * W + 9, -1, -1, W + 2, -1], 1, "bfloat16", 2),
    "all-padded": ([-1, -1], 0, "bfloat16", None),
    # half a block of summaries behind a ring block that is not full
    "half-blocks": ([W + 13, 3 * W + 30], 1, "float32", 4),
}


def _inputs(name):
    """``(q, poisoned pools, clean pools, tables, state rows, positions,
    live rows)``.  The poisoned pools are NaN or infinite wherever the
    batch's queries may not read: ring entries past ``t mod W``, summary rows
    at and past ``t // W * W / c``, every page no table names (the trash
    page among them), every other slot's ring (the trash slot's among
    them), and the whole of every other layer; the clean pools hold zeros
    there, for the definition to read (a probability of zero times a NaN is
    a NaN)."""
    positions, layer, dtype, _bp = CASES[name]
    positions = np.asarray(positions, np.int64)
    live = positions >= 0
    b = len(positions)
    rng = np.random.default_rng(sum(map(ord, name)))
    num_pages = 1 + (int(live.sum()) + 1) * ROW_PAGES
    ids = rng.permutation(np.arange(1, num_pages))
    tables = np.zeros((b, ROW_PAGES), np.int32)
    tables[live] = ids[:int(live.sum()) * ROW_PAGES].reshape(-1, ROW_PAGES)
    rows = np.zeros((b,), np.int32)
    rows[live] = rng.permutation(np.arange(1, SLOTS + 1))[:int(live.sum())]
    page_live = np.zeros((LAYERS, num_pages, PAGE), bool)
    ring_live = np.zeros((LAYERS, SLOTS + 1, W), bool)
    for i in np.flatnonzero(live):
        t = int(positions[i])
        closed = t // W * PER_WINDOW
        page_live[layer, tables[i]] = (
            np.arange(ROW_PAGES * PAGE) < closed).reshape(ROW_PAGES, PAGE)
        ring_live[layer, rows[i], :t % W + 1] = True
    keys = jax.random.split(jax.random.PRNGKey(b + layer), 5)
    shapes = ((LAYERS, num_pages, PAGE),) * 2 + ((LAYERS, SLOTS + 1, W),) * 2
    lives = (page_live,) * 2 + (ring_live,) * 2
    pools = [jax.random.normal(k, s + (HEADS, WIDTH)).astype(dtype)
             for k, s in zip(keys, shapes)]
    # keys and values of both kinds: NaN in one, infinities in the other
    poison = (jnp.nan, jnp.inf, -jnp.inf, jnp.nan)
    bad = [jnp.where(at[..., None, None], p, x).astype(dtype)
           for p, at, x in zip(pools, lives, poison)]
    clean = [jnp.where(at[..., None, None], p, 0).astype(dtype)
             for p, at in zip(pools, lives)]
    q = jax.random.normal(keys[4], (b, HEADS, WIDTH), jnp.float32)
    return (q, bad, clean, jnp.asarray(tables), jnp.asarray(rows),
            jnp.asarray(np.maximum(positions, 0), jnp.int32), live)


@pytest.mark.parametrize("name", list(CASES))
def test_live_ring_entries_and_closed_windows_summaries_and_nothing_else(
        name):
    """The kernel's output for every live row is the definition's over the
    row's whole ring and gathered summaries, within the stored dtype's
    tolerance, with everything a query may not read NaN or infinite: dead
    entries inside a fetched ring block are masked and as values made zeros;
    ring blocks past ``t mod W``, the open window's own summary rows, pages
    no table names, other slots' rings and other layers are not read.  A
    padded row, wherever it stands, gives finite zeros.  The pools keep
    their bits."""
    _positions, layer, dtype, block_pages = CASES[name]
    q, bad, clean, tables, rows, positions, live = _inputs(name)
    net, pages = _block(dtype)
    before = [np.asarray(p.astype(jnp.float32)) for p in bad]
    got = eva_attention(q, *bad, layer, tables, rows, positions,
                        row_tokens=C, block_pages=block_pages,
                        interpret=True)
    assert got.shape == q.shape and got.dtype == jnp.float32
    got = np.asarray(got)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[~live], 0.0)
    for pool, was in zip(bad, before):
        np.testing.assert_array_equal(np.asarray(pool.astype(jnp.float32)),
                                      was)
    if not live.any():
        return
    # on the CPU the door is the definition: the whole ring, every reserved
    # summary row, the block's own attend_row
    with jax.default_matmul_precision("highest"):
        want = np.asarray(pages.attend_window(
            tuple(clean), layer, tables, rows, positions, q,
            net.attend_row))
    assert np.abs(got[live] - want[live]).max() <= \
        TOL[dtype] * np.abs(want[live]).max()
    # and what the definition says: a row in its first window has no
    # summary column, so the summaries cannot have entered it
    firsts = live & (np.asarray(positions) < W)
    if firsts.any():
        moved = [p if j >= 2 else p + 1 for j, p in enumerate(bad)]
        again = np.asarray(eva_attention(
            q, *moved, layer, tables, rows, positions, row_tokens=C,
            block_pages=block_pages, interpret=True))
        np.testing.assert_array_equal(again[firsts], got[firsts])


def test_layer_is_an_operand_not_the_kernels_text():
    """One traced function serves every layer: the layer is a scalar the
    kernel prefetches, so every layer of every step program lowers one
    body."""
    q, _bad, clean, tables, rows, positions, _live = _inputs(
        "third-beside-first")
    # every layer live: the case's other layer holds zeros
    pools = [p.at[0].set(p[1] * 0.5) for p in clean]

    @jax.jit
    def f(layer):
        return eva_attention(q, *pools, layer, tables, rows, positions,
                             row_tokens=C, interpret=True)

    outs = [np.asarray(f(jnp.int32(layer))) for layer in range(LAYERS)]
    assert f._cache_size() == 1
    assert np.abs(outs[0] - outs[1]).max() > 0.01


def test_the_entry_the_step_just_wrote_is_attended():
    """Entry ``t mod W`` itself is among what a query reads, wherever in a
    block it lies: written along the query of head 0 and long, it takes all
    of that head's softmax, and the head's context is its value."""
    q, _bad, clean, tables, rows, positions, _live = _inputs("half-blocks")
    went = positions % W
    wrote = 30 * q[:, 0] / jnp.linalg.norm(q[:, 0], axis=-1, keepdims=True)
    ring_k = clean[2].at[1, rows, went, 0].set(wrote)
    got = np.asarray(eva_attention(
        q, clean[0], clean[1], ring_k, clean[3], 1, tables, rows, positions,
        row_tokens=C, block_pages=2, interpret=True))
    want = np.asarray(clean[3][1, rows, went, 0])
    assert np.abs(got[:, 0] - want).max() < 1e-3 * np.abs(want).max()


def test_the_door_counts_what_is_lowered_and_takes_rings_and_pages_only():
    """``PageFormat.attend_window`` lowered for the CPU is the definition
    and counts ``decode.attn.eva.lowered{kind="plain"}`` once a call (for the
    chip it is the kernel: ``tests/test_chip_compile.py``); the window and
    the tokens a row stands for are the layout's, not arguments.  A format
    without a slot's two rings, or with quantized pools, has no such door
    and is refused in words; a block of pages that does not divide the
    window is refused by the kernel."""
    q, _bad, clean, tables, rows, positions, _live = _inputs(
        "third-beside-first")
    net, pages = _block("float32")
    fn = jax.jit(lambda q, *pools: pages.attend_window(
        pools, 1, tables, rows, positions, q, net.attend_row))
    assert counted("decode.attn.eva.lowered", lambda: fn.lower(q, *clean)) \
        == {'{kind="plain",rows="2"}': 1}
    layout = net.cache_layout()
    for broken in (dict(layout, state=None),
                   dict(layout, state=dict(layout["state"], arrays=layout[
                       "state"]["arrays"][:1])),
                   dict(layout, row_shape=None)):
        with pytest.raises(ValueError, match="attend_window reads raw K and "
                                             "V pools of rows by head"):
            PageFormat(broken, page_size=PAGE).attend_window(
                tuple(clean), 0, tables, rows, positions, q, net.attend_row)
    with pytest.raises(ValueError, match="does not divide the window"):
        eva_attention(q, *clean, 0, tables, rows, positions, row_tokens=C,
                      block_pages=3, interpret=True)
