"""The EVA-attention byte-level decode block against its plain reference
(``perf/reference/evabyte.py``), at a small size on the CPU with seeded
weights (windows of 32 positions, chunks of 4): prefill (by query blocks) then
decoding through the slot's ring and the summary pages against the
reference's full forward pass (ONE dense masked softmax over ``[S keys | S / c
summaries]``), all eight prediction heads; a prompt that ends mid-chunk and
mid-window decoded past two window ends; a prompt of exactly one window; a row
in its first window beside a row in its third in one step; a slot reused after
a longer owner; the summaries dropped and the pooling made uniform (so the
comparison can fail); the page row that stands for ``chunk_size`` tokens in
the cache's and the runtime's arithmetic; what the block refuses; the
session's stream and counters.

Tolerances, as a share of the largest logit.  ``float32`` runs every product
at the highest precision, so the program and the reference differ by
summation order alone (a ring and gathered pages against one dense row of
scores): 1e-5 (measured 3e-7 to 5e-7).  This is the run that ties the
mathematics down: without the summary columns the same comparison reads a
median of 0.46 and a largest of 1.05, with uniform pooling 0.39 and 1.10.
``bfloat16`` rounds both inputs of every product to 8 bits of mantissa, the
ring entries and the summary rows once more; over six products a layer and
two layers that is about sqrt(12) * 2**-9 = 0.7% at a real width and more at
64 wide: 5% (measured: a median of 0.6% and a largest of 1.4% over 76
positions).  The block is dense: no expert choice flips.
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.serving.decode import (DecodeRuntime,  # noqa: E402
                                      DecodeSession, EvaLM, PagedKVCache,
                                      pages_needed)
from decode_block_harness import (MAX_PAGES, PAGE, Kit,  # noqa: E402
                                  decode_logits, new_cache, programs,
                                  relative_errors, table_row)
from perf.reference import evabyte as ref  # noqa: E402
from perf.systems import eva_gateway as system_mod  # noqa: E402

TOL = {"float32": 1e-5, "bfloat16": 5e-2}
W, C = 32, 4
REF_PAD = 128           # the reference's sequences: four windows
CONTEXT = MAX_PAGES * PAGE * C      # 8 pages of 8 rows of 4 tokens: 256

KIT = Kit(ref, system_mod, TOL)
assert_close = KIT.assert_close


def tiny_cfg(dtype="bfloat16"):
    """The family's keys at a size the CPU runs in a second: two layers, four
    heads of 16, windows of 32 and chunks of 4 (eight chunks a window, as the
    published 128 are many to a window); 0.2 for the initialiser so that the
    logits are of order 1; the published draws of ``phi``, ``mu`` and the
    norms' offsets."""
    return {"hidden_size": 64, "n_layer": 2, "num_attention_heads": 4,
            "num_key_value_heads": 4, "intermediate_size": 128,
            "vocab_size": 97, "window_size": W, "chunk_size": C,
            "num_pred_heads": 8, "rope_theta": 1e5, "rms_norm_eps": 1e-5,
            "initializer_range": 0.2,
            "draws": {"norm_offset_std": 0.1, "phi_std": 1.0, "mu_std": 0.75},
            "precision": {"weights": dtype}}


def build(cfg, **kw):
    return KIT.build(cfg, max_length=CONTEXT, **kw)


def reference_logits(w, cfg, tokens, first, precision="float32"):
    """The reference's logits ``(positions, heads, vocab)`` of positions
    ``first ..`` of ``tokens``."""
    padded = np.zeros((REF_PAD,), "int32")
    padded[:len(tokens)] = tokens
    return np.asarray(ref.forward(w, cfg, jnp.asarray(padded), precision,
                                  query_block=32))[first:len(tokens)]


# ------------------------------------------------- (a) against the reference
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n_prompt,seq_pad,n_total", [
    (45, 64, 120),      # ends mid-chunk, mid-window; decoded past 64 and 96
    (32, 32, 70),       # exactly one window: the ring starts empty
    (3, 32, 40),        # a first window with no summary column, then one
    (70, 96, 100),      # the prompt's own third window over two closed ones
])
def test_prefill_then_decode_matches_reference(dtype, n_prompt, seq_pad,
                                               n_total):
    """Prefill by query blocks, the commit's ring and summaries (those of the
    open window's complete chunks too), then single steps through ring and
    pages: head 0 of every position is the reference's full forward over the
    sequence; in float32 the last step's drafts are the other heads' first
    choices."""
    cfg = tiny_cfg(dtype)
    net, w = build(cfg)
    tokens = np.random.default_rng(7).integers(0, 97, n_total)
    got, extras, _p = decode_logits(net, tokens, n_prompt,
                                    pages=[3, 5, 7, 9], slot_row=2,
                                    seq_pad=seq_pad)
    want = reference_logits(w, cfg, tokens, n_prompt - 1)
    assert_close(got, want[:, 0], dtype)
    drafts, counts = (np.asarray(e) for e in extras)
    t = n_total - 1
    assert counts.tolist() == [t % W + 1, t // W * (W // C),
                               int(t % W == W - 1), 1]
    if dtype == "float32":
        assert drafts[0].tolist() == want[-1, 1:].argmax(-1).tolist()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_every_prediction_head_is_the_reference(dtype):
    """All eight heads at the prompt's last position, through the program's
    own prefill and head: head ``i`` is columns ``i * vocab ..`` of the
    published ``lm_head``."""
    cfg = tiny_cfg(dtype)
    net, w = build(cfg)
    p = net._params_dict(net.param_leaves())
    tokens = np.random.default_rng(9).integers(0, 97, 77)
    prompt = np.zeros((1, 96), "int32")
    prompt[0, :77] = tokens
    lengths = jnp.asarray([77], "int32")
    got = jax.jit(lambda p_, t, n: net.head_logits(
        p_, net.prefill_hidden(p_, t, n)[0]))(p, jnp.asarray(prompt), lengths)
    want = reference_logits(w, cfg, tokens, 76)[0]
    assert got.shape == (1, 8, 97)
    err = np.abs(np.asarray(got[0]) - want).max(-1) / np.abs(want).max()
    assert err.max() <= TOL[dtype], err
    # and the logits the runtime samples from are head 0 of these
    served = jax.jit(net.prefill_math)(p, jnp.asarray(prompt), lengths)[0]
    np.testing.assert_array_equal(np.asarray(served), np.asarray(got[:, 0]))


@pytest.mark.parametrize("what", ["summaries_off", "pool_uniform"])
def test_a_broken_mechanism_is_far_outside_the_tolerance(what):
    """The sound block passes at 1e-5; the reference without its summary
    columns, or with plain means for both poolings and no ``mu``, is tens of
    percent away once the context has a closed window."""
    cfg = tiny_cfg("float32")
    net, w = build(cfg)
    tokens = np.random.default_rng(7).integers(0, 97, 120)
    got, _x, _p = decode_logits(net, tokens, 20, pages=[3, 5, 7, 9],
                                slot_row=2, seq_pad=32)
    sound = relative_errors(got, reference_logits(w, cfg, tokens, 19)[:, 0])
    broken = relative_errors(
        got, reference_logits(w, cfg, tokens, 19, what)[:, 0])
    assert sound.max() <= TOL["float32"]
    assert np.median(broken) > 0.05 and broken.max() > 0.3
    # positions 19..31 are the first window's: no summary is seen yet, and
    # neither control moves them
    assert broken[:32 - 19].max() <= TOL["float32"]
    assert broken[32 - 19:].min() > 100 * TOL["float32"]


def _prefilled(net, cache, pools, tokens, n_prompt, pages, slot_row, seq_pad):
    """Prefill and commit one prompt; ``(logits row, pools)``."""
    p = net._params_dict(net.param_leaves())
    prefill, commit, _step = programs(net, cache.pages)
    prompt = np.zeros((1, seq_pad), "int32")
    prompt[0, :n_prompt] = tokens[:n_prompt]
    lengths = jnp.asarray([n_prompt], "int32")
    logits, *state = prefill(p, jnp.asarray(prompt), lengths)
    pools = commit(tuple(state), lengths,
                   jnp.asarray(table_row(pages, slot_row)[None]), pools)
    return np.asarray(logits[0]), pools


def test_a_row_in_its_first_window_beside_a_row_in_its_third():
    """Two sequences stepped TOGETHER, one at positions 10.. of its first
    window (no summary column, nine ring entries masked in) and one at 70..
    of its third (sixteen summaries and a ring that restarts at 96): each row
    is its own reference, and the step's counts add the two."""
    cfg = tiny_cfg("float32")
    net, w = build(cfg)
    p = net._params_dict(net.param_leaves())
    cache = new_cache(net)
    _pre, _com, step = programs(net, cache.pages)
    rng = np.random.default_rng(11)
    seqs = [rng.integers(0, 97, 10 + 30), rng.integers(0, 97, 70 + 30)]
    starts, pads = [10, 70], [32, 96]
    rows = [([2, 4], 1), ([1, 3, 5, 6], 3)]
    pools, got = cache.pools, [[], []]
    for r in range(2):
        first, pools = _prefilled(net, cache, pools, seqs[r], starts[r],
                                  rows[r][0], rows[r][1], pads[r])
        got[r].append(first)
    tables = jnp.asarray(np.stack([table_row(*rows[0]), table_row(*rows[1])]))
    for k in range(29):
        tok = np.asarray([seqs[r][starts[r] + k] for r in range(2)], "int32")
        pos = np.asarray([starts[r] + k for r in range(2)], "int32")
        logits, pools, extras = step(p, jnp.asarray(tok), jnp.asarray(pos),
                                     tables, pools)
        for r in range(2):
            got[r].append(np.asarray(logits[r]))
        counts = np.asarray(extras[1]).tolist()
        assert counts[0] == sum(int(t) % W + 1 for t in pos)
        assert counts[1] == sum(int(t) // W * (W // C) for t in pos)
        assert counts[2:] == [sum(int(t) % W == W - 1 for t in pos), 2]
    for r in range(2):
        want = reference_logits(w, cfg, seqs[r][:starts[r] + 29],
                                starts[r] - 1)[:, 0]
        assert relative_errors(np.stack(got[r]), want).max() <= TOL["float32"]


def test_a_slot_reused_after_a_longer_owner_serves_the_same_logits():
    """Nothing zeroes a slot or a page between owners.  A sequence of 110
    positions leaves a full ring and three and a half windows of summaries;
    a shorter one given the same slot and pages reads none of it: the
    commit overwrites the ring whole, entries past ``position mod W`` are
    masked, and summaries past the closed windows are not seen.  The same
    logits as on zeroed pools, and as on pools of junk, bit for bit."""
    net, _w = build(tiny_cfg("float32"))
    rng = np.random.default_rng(4)
    owner, tokens = rng.integers(0, 97, 110), rng.integers(0, 97, 75)
    pages = [1, 2, 5, 6]
    clean, _x, _p = decode_logits(net, tokens, 37, pages=pages, slot_row=2,
                                  seq_pad=64)
    cache = new_cache(net)
    _l, _x, used = decode_logits(net, owner, 70, pages=pages, slot_row=2,
                                 seq_pad=96, cache=cache, pools=cache.pools)
    again, _x, _p = decode_logits(net, tokens, 37, pages=pages, slot_row=2,
                                  seq_pad=64, cache=cache, pools=used)
    np.testing.assert_array_equal(clean, again)
    junk = tuple(jnp.full(p.shape, 3.0 + j, p.dtype)
                 for j, p in enumerate(new_cache(net).pools))
    dirty, _x, _p = decode_logits(net, tokens, 37, pages=pages, slot_row=2,
                                  seq_pad=64, cache=cache, pools=junk)
    np.testing.assert_array_equal(clean, dirty)


def test_commit_hands_over_the_open_window_and_every_complete_chunk():
    """A prompt of 45 = one window and 13: the ring holds positions 32..44 at
    entries 0..12 and zeros behind them; summary rows 0..10 (45 // 4 = 11
    complete chunks, three of them the OPEN window's) are written where the
    table says, row 11 (the partial chunk) and everything past it are not."""
    net, _w = build(tiny_cfg("float32"))
    p = net._params_dict(net.param_leaves())
    cache = new_cache(net)
    tokens = np.random.default_rng(3).integers(0, 97, 45)
    marked = tuple(jnp.full(x.shape, 7.0, x.dtype) for x in cache.pools)
    _first, pools = _prefilled(net, cache, marked, tokens, 45, [4, 9], 3, 64)
    state = jax.jit(net.prefill_math)(
        p, jnp.asarray(np.pad(tokens, (0, 19))[None]),
        jnp.asarray([45], "int32"))[1:]
    first = cache.pages.state.first
    for layer in range(2):
        kbar, _vbar, ring_k, _ring_v = (
            np.asarray(x) for x in state[4 * layer:4 * layer + 4])
        ring = np.asarray(pools[first][layer, 3])
        np.testing.assert_array_equal(ring, ring_k[0])
        assert ring[:13].any(axis=(1, 2)).all() and not ring[13:].any()
        rows = np.asarray(pools[0][layer])[[4, 9]].reshape(2 * PAGE, 4, 16)
        np.testing.assert_array_equal(rows[:11], kbar[0, :11])
        assert (rows[11:] == 7.0).all()
    # no other slot, and no page the table does not name, was touched
    assert (np.asarray(pools[first][:, 2]) == 7.0).all()
    assert (np.asarray(pools[0][:, 5]) == 7.0).all()


# ------------------------------------------------- (b) the stride of a row
def test_a_page_row_stands_for_a_chunk_in_cache_and_runtime():
    net, _w = build(tiny_cfg(), fresh=True)
    layout = net.cache_layout()
    assert layout["row_tokens"] == C and layout["layers"] == 2
    assert [n for n, _w_, _d in layout["pools"]] == ["kbar", "vbar"]
    cache = new_cache(net)
    assert cache.page_size == cache.pages.page_size == PAGE
    assert cache.page_tokens == cache.pages.page_tokens == PAGE * C
    assert cache.context_length == CONTEXT
    # pools of ROWS, stored by head: 24 pages of 8 rows of 4 heads of 16; a
    # slot's rings behind them
    assert layout["row_shape"] == (4, 16)
    assert [x.shape for x in cache.pools] == \
        [(2, 24, PAGE, 4, 16)] * 2 + [(2, 5, W, 4, 16)] * 2
    assert cache.kv_bytes_per_token * C == 2 * 2 * 64 * 2 == \
        cache.pages.row_bytes
    assert cache.page_bytes == cache.pages.row_bytes * PAGE
    # 100 positions written: 4 pages of 32 tokens, not 13 of 8
    assert pages_needed(90, 11, cache.page_tokens) == 4
    rt = DecodeRuntime(net, page_size=PAGE, batch_buckets=(1, 4),
                       seq_buckets=(32, 64), warm=False)
    assert rt.cache.context_length == CONTEXT
    assert rt.cache.max_pages_per_seq == MAX_PAGES
    assert rt.cache.max_slots == 4 and rt.cache.table_width == MAX_PAGES + 1
    assert rt.prefill_batch_buckets == (1,) == (net.max_prefill_batch,)
    # a layer's summaries and rings after another's
    assert [s for s, _d in net.prefill_state(1, 64)] == \
        ([(1, 16, 4, 16)] * 2 + [(1, W, 4, 16)] * 2) * 2


@pytest.mark.parametrize("what,kwargs,match", [
    ("int8 pool", {"kv_dtype": "int8"}, "int8/fp8 pool"),
    ("fp8 pool", {"kv_dtype": "fp8_e4m3"}, "int8/fp8 pool"),
    ("verify ladder", {"spec_buckets": (2,)}, "cannot speculate"),
    ("drafter", {"drafter": "ngram"}, "cannot speculate"),
])
def test_what_the_block_does_not_support_says_so(what, kwargs, match):
    net, _w = build(tiny_cfg(), fresh=True)
    with pytest.raises(ValueError, match=match):
        DecodeSession(net, page_size=PAGE, batch_buckets=(1,),
                      seq_buckets=(32,), warm=False, start=False, **kwargs)


def test_mesh_and_bad_sizes_say_so():
    net, _w = build(tiny_cfg(), fresh=True)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("model",))
    with pytest.raises(ValueError, match="slot pools, which are not sharded"):
        PagedKVCache(layout=net.cache_layout(), mesh=mesh)
    with pytest.raises(ValueError, match="slot pools, which are not sharded"):
        DecodeSession(net, page_size=PAGE, batch_buckets=(1,),
                      seq_buckets=(32,), warm=False, start=False, mesh=mesh)
    with pytest.raises(ValueError, match="a window closes whole"):
        EvaLM(window_size=30, chunk_size=4)
    with pytest.raises(ValueError, match="whole, even head width"):
        EvaLM(hidden_size=60, num_attention_heads=4)
    cfg = dict(tiny_cfg(), num_key_value_heads=2)
    with pytest.raises(ValueError, match="num_key_value_heads=2 differs"):
        build(cfg)


# ------------------------------------------------- through the normal path
@pytest.fixture(scope="module")
def session():
    net, w = build(tiny_cfg("float32"), seed=5, fresh=True)
    sess = DecodeSession(net, page_size=PAGE, batch_buckets=(1, 2, 4),
                         seq_buckets=(32, 64))
    yield sess, net, w
    sess.close(drain=False)


def test_session_serves_the_reference_greedy_stream(session):
    """Gateway's session, scheduler, runtime and cache: five requests over
    four slots (so one waits for a slot that another leaves, its ring and
    summaries in it), prompts under, at and over one window, two of them
    decoding across a window's end: each produces the reference's own greedy
    continuation (float32, so the argmax is the reference's)."""
    sess, _net, w = session
    cfg = tiny_cfg("float32")
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, 97, n).tolist() for n in (5, 27, 32, 41, 60)]
    futs = [sess.submit(p, max_new_tokens=10) for p in prompts]
    # a reservation counts pages of 32 tokens: 60 + 9 written positions
    assert [pages_needed(len(p), 10, sess.cache.page_tokens)
            for p in prompts] == [1, 2, 2, 2, 3]
    for prompt, fut in zip(prompts, futs):
        seq = list(prompt)
        for _ in range(10):
            logits = reference_logits(w, cfg, seq, len(seq) - 1)
            seq.append(int(np.argmax(logits[0, 0])))
        assert fut.result(timeout=120).token_ids == seq[len(prompt):]
    s = sess.stats()
    assert s["pages_in_use"] == 0 and s["slots_in_use"] == 0
    assert s["state_slots_live"] == 0 and s["state_bytes"] > 0
    assert s["prefix_skipped"] >= 5 and s["prefix_hits"] == 0


def test_step_counters_ride_the_fetch(session):
    sess, net, _w = session
    mx.telemetry.enable()
    try:
        mx.telemetry.reset()
        sess.generate(list(range(1, 31)), max_new_tokens=6, timeout=120)
        snap = mx.telemetry.snapshot()
        c = snap["counters"]
    finally:
        mx.telemetry.disable()
    steps = c["decode.steps"]
    assert steps == 5           # positions 30..34: the first token is the prefill's
    assert c["decode.eva.layer_steps"] == steps * 2
    # ring entries live at 30, 31 | 32, 33, 34: 31 + 32 + 1 + 2 + 3, a layer
    assert c["decode.eva.ring_rows"] == 69 * 2
    # the closed window's eight summaries, seen by the last three steps
    assert c["decode.eva.summary_rows"] == 3 * 8 * 2
    assert c["decode.eva.summary_rows_written"] == steps * 2
    assert c["decode.eva.windows_closed"] == 1
    g = snap["gauges"]
    assert g["decode.eva.live_summary_rows"] == 8
    assert g["decode.eva.live_ring_bytes"] == 3 * net.entry_bytes == \
        3 * 2 * 2 * 64 * 4
    assert c["decode.prefix.skipped"] == 1
    assert "decode.prefix_hits" not in c and "decode.prefix_misses" not in c
    assert g["decode.state_slots_live"] == 0
    assert g["decode.state_bytes"] == sess.cache.state_bytes
    # on the CPU the step's attention is the definition, ``attend_row`` over
    # the gathered ring and summaries, counted once a layer where the
    # program is lowered (for the chip it is the kernel:
    # ``tests/test_chip_compile.py``)
    from mxnet_tpu.test_utils import counted
    step = programs(net, sess.cache.pages)[2]
    rows = jnp.zeros((2,), "int32")
    lowered = counted("decode.attn.eva.lowered", lambda: step.lower(
        net._params_dict(net.param_leaves()), rows, rows,
        jnp.zeros((2, sess.cache.table_width), "int32"), sess.cache.pools))
    assert lowered == {'{kind="plain",rows="2"}': net.num_layers}
