"""The sliding-window / global attention, routed-expert decode block against
its plain reference (``perf/reference/mimo_v2.py``), at a small size on the
CPU with seeded weights: prefill (by query blocks) then decoding through the
paged global K/V and the window layers' rings against the reference's full
forward pass (dense masked attention), at contexts several times the window
and rings that have wrapped more than once; a wrong window and a missing
sink (so the comparison can fail); batch composition, page and slot
placement; dirty slots and recycled pages; the selection bias; the shares of
a 256-expert layer adding up to the uncut layer; what the block refuses; the
two kinds of attention state in the cache's ``stats()``; and the session's
stream and counters.

Tolerances, as a share of the largest logit.  ``float32`` runs every product
at the highest precision, so the program and the reference differ by
summation order alone (blocked against dense softmax, grouped against
per-expert products): 5e-5 (measured 1.0e-6 to 1.4e-6 over prompts of 1 to
21 tokens at seed 8).  This is the run that ties the mathematics down: a
window of 9 for 8 reads a median of 0.16 and a largest of 0.28, a missing
sink 0.08 and 0.18, window layers that read everything 0.31 and 0.48.
``bfloat16``
rounds both inputs of every product to 8 bits of mantissa (2**-9 relative),
the K/V rows once more; over some seven products a layer and five layers
that is about sqrt(35) * 2**-9 = 1.2% at a real width and more at 64 wide:
5% (measured: a median of 0.9% to 1.3% over the positions of a sequence,
and one position of 44 at 10.6%, a flipped choice).  That holds where the
program's expert choices are the reference's; where two selection scores lie
within bfloat16's noise the choice flips and that token's logits move by 10
to 50% of their scale, which no tolerance on logits covers and none is
claimed (``tests/test_latent_moe.py`` says the same of the shared expert
layer): the bfloat16 comparisons allow one position in ten to flip, the
router's scores are float32 so that it is rare at the real width, and the
benchmark counts the served tokens it moves.
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
from mxnet_tpu.parallel import moe  # noqa: E402
from mxnet_tpu.serving.decode import (DecodeRuntime,  # noqa: E402
                                      DecodeSession, PagedKVCache,
                                      WindowMoELM)
from mxnet_tpu.serving.decode import window_moe  # noqa: E402
from decode_block_harness import (MAX_PAGES, PAGE, Kit,  # noqa: E402
                                  decode_logits, new_cache, programs,
                                  relative_errors, table_row)
from perf.harness.weights import seed_key  # noqa: E402
from perf.reference import mimo_v2 as ref  # noqa: E402
from perf.systems import window_moe_gateway as system_mod  # noqa: E402

TOL = {"float32": 5e-5, "bfloat16": 5e-2}
WINDOW = 8
REF_PAD = 48            # the reference's sequences, whole blocks of 16

# built and compiled once a configuration: tests/decode_block_harness.py
KIT = Kit(ref, system_mod, TOL)
build, assert_close = KIT.build, KIT.assert_close


def tiny_cfg(dtype="bfloat16", held=(0, 1, 2, 3, 8, 9), window=WINDOW):
    """The family's keys at a size the CPU runs in a second: five layers
    (global + dense, window, window, global, window: both kinds of
    attention under both kinds of MLP but window + dense); 0.2 for the
    initialiser so that the logits are of order 1; a sink near the rows'
    largest scores and a selection bias as wide as the gaps between router
    scores, so that both matter."""
    return {"hybrid_layer_pattern": [0, 1, 1, 0, 1, 1],
            "moe_layer_freq": [0, 1, 1, 1, 1, 1], "n_layer": 5,
            "hidden_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 1, "swa_num_key_value_heads": 2,
            "head_dim": 24, "v_head_dim": 16, "partial_rotary_factor": 0.334,
            "rope_theta": 1e7, "swa_rope_theta": 1e4,
            "sliding_window": window, "attention_value_scale": 0.707,
            "intermediate_size": 128, "moe_intermediate_size": 32,
            "n_routed_experts": len(held), "held_experts": list(held),
            "published": {"n_routed_experts": 16}, "num_experts_per_tok": 4,
            "n_group": 1, "topk_group": 1, "routed_scaling_factor": None,
            "layernorm_epsilon": 1e-5, "vocab_size": 97,
            "initializer_range": 0.2, "sink_bias": {"mean": 1.0, "std": 1.0},
            "selection_bias_std": 0.1, "precision": {"weights": dtype}}


def reference_logits(w, cfg, tokens, first, precision="float32"):
    """The reference's logits of positions ``first ..`` of ``tokens``."""
    padded = np.zeros((REF_PAD,), "int32")
    padded[:len(tokens)] = tokens
    return np.asarray(ref.forward(w, cfg, jnp.asarray(padded), precision,
                                  query_block=16))[first:len(tokens)]


# ------------------------------------------------- (a) against the reference
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n_prompt,seq_pad", [(1, 8), (5, 8), (13, 16),
                                              (21, 32)])
def test_prefill_then_decode_matches_reference(dtype, n_prompt, seq_pad):
    """Prompts shorter than the window (1, 5 of 8), between one and two
    (13) and past two (21: the band reads a block and the one before it,
    the global layer maps three query blocks), handed to pages and a ring
    as of the true length; then single steps to a context of 44 = 5.5
    windows, so every ring wraps more than twice and the paged context
    crosses five pages: the reference's full forward over the sequence."""
    cfg = tiny_cfg(dtype)
    net, w = build(cfg)
    tokens = np.random.default_rng(7).integers(0, 97, 44)
    got, _x, _p = decode_logits(net, tokens, n_prompt,
                                pages=[3, 5, 7, 9, 11, 13], slot_row=2,
                                seq_pad=seq_pad)
    assert_close(got, reference_logits(w, cfg, tokens, n_prompt - 1), dtype)


def test_float32_is_much_tighter_than_bfloat16():
    tokens = np.random.default_rng(8).integers(0, 97, 30)
    err = {}
    for dtype in ("float32", "bfloat16"):
        cfg = tiny_cfg(dtype)
        net, w = build(cfg)
        got, _x, _p = decode_logits(net, tokens, 6, pages=[1, 2, 3, 4],
                                    slot_row=1)
        err[dtype] = np.median(relative_errors(
            got, reference_logits(w, cfg, tokens, 5)))
    assert err["float32"] < 5e-5 < 5e-4 < err["bfloat16"] < 2.5e-2


@pytest.mark.parametrize("what", ["window_off", "sink_off", "window_9"])
def test_a_wrong_window_or_a_missing_sink_is_far_outside_the_tolerance(what):
    """So the comparison above can fail: the reference with the window
    layers reading the whole context, without the sink's column, or with a
    window of 9 for 8, is nowhere near the block (and so not near the sound
    reference) once the context passes the window."""
    cfg = tiny_cfg("float32")
    net, w = build(cfg)
    tokens = np.random.default_rng(7).integers(0, 97, 44)
    got, _x, _p = decode_logits(net, tokens, 13, pages=[3, 5, 7, 9, 11, 13],
                                slot_row=2)
    if what == "window_9":
        wrong = reference_logits(w, tiny_cfg("float32", window=9), tokens, 12)
    else:
        wrong = reference_logits(w, cfg, tokens, 12, precision=what)
    err = relative_errors(got, wrong)
    assert np.median(err) > 100 * TOL["float32"] and err.max() > 1e-2
    # before a context passes the window, a window of 9 is a window of 8
    if what == "window_9":
        head, _x, _p = decode_logits(net, tokens[:8], 3, pages=[1],
                                     slot_row=1, seq_pad=8)
        same = reference_logits(w, tiny_cfg("float32", window=9),
                                tokens[:8], 2)
        assert relative_errors(head, same).max() <= TOL["float32"]


def test_a_block_with_the_wrong_window_fails_the_reference():
    cfg = tiny_cfg("float32")
    w = ref.weights(cfg, seed_key(8, stream=1))
    net = system_mod.block(tiny_cfg("float32", window=9), 64, dict(w),
                           jax.devices()[0])
    tokens = np.random.default_rng(7).integers(0, 97, 30)
    got, _x, _p = decode_logits(net, tokens, 13, pages=[3, 5, 7, 9],
                                slot_row=2, seq_pad=18)
    err = relative_errors(got, reference_logits(w, cfg, tokens, 12))
    assert err.max() > 100 * TOL["float32"]


def test_prefill_hands_over_the_ring_as_of_the_true_length():
    """The same prompt of 11 under three paddings: what prefill emits for
    the window layers is the prompt's LAST 8 tokens, each at its position
    modulo the window, whatever the bucket's end; a prompt of 5 leaves the
    entries it has no token for at zero."""
    net, _w = build(tiny_cfg("float32"))
    p = net._params_dict(net.param_leaves())
    tokens = np.random.default_rng(5).integers(0, 97, 11)
    prefill = programs(net, new_cache(net).pages)[0]
    got = []
    for pad in (16, 24, 32):
        prompt = np.full((1, pad), 96, "int32")       # junk behind the prompt
        prompt[0, :11] = tokens
        _l, k_rows, _v, ring_k, ring_v = prefill(
            p, jnp.asarray(prompt), jnp.asarray([11], "int32"))
        assert ring_k.shape == (3, 1, WINDOW, 48) and \
            ring_v.shape == (3, 1, WINDOW, 32) and \
            k_rows.shape == (2, 1, pad, 24)
        got.append((np.asarray(ring_k), np.asarray(ring_v)))
    for ring_k, ring_v in got[1:]:
        np.testing.assert_allclose(ring_k, got[0][0], rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(ring_v, got[0][1], rtol=1e-5, atol=1e-6)
    # positions 3..10 stand at entries 3..7, 0..2: entry 2 holds position 10
    short = np.zeros((1, 16), "int32")
    short[0, :5] = tokens[:5]
    _l, _k, _v, ring5, _rv = prefill(p, jnp.asarray(short),
                                     jnp.asarray([5], "int32"))
    assert np.abs(np.asarray(ring5)[:, 0, :5]).min(-1).min() > 0
    assert np.abs(np.asarray(ring5)[:, 0, 5:]).max() == 0
    # keys carry their position's rotation: the first 5 of both prompts
    # are the same tokens at the same positions, the ring of 11 holds other
    # positions there
    assert np.abs(got[0][0][:, 0, 3:5] - np.asarray(ring5)[:, 0, 3:5]
                  ).max() < 1e-5
    assert np.abs(got[0][0][:, 0, :3] - np.asarray(ring5)[:, 0, :3]
                  ).max() > 1e-2


# --------------------------------------- (b) batch, pages and slot placement
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("batch,row,slot_row", [(4, 2, 3), (2, 0, 4),
                                                (4, 3, 1)])
def test_batch_composition_and_slot_placement_do_not_change_a_row(
        dtype, batch, row, slot_row):
    """Solo in a one-row program against a row of a padded batch (of 4 and
    of 2 rows; the live row first, in the middle and last, padded rows
    anywhere about it) in other pages and another slot: the step finds a
    row's ring by its state row, wherever the row stands."""
    net, _w = build(tiny_cfg(dtype))
    tokens = np.random.default_rng(9).integers(0, 97, 28)
    solo, _x, _p = decode_logits(net, tokens, 5, pages=[1, 2, 3, 4],
                                 slot_row=1)
    moved, _x, _p = decode_logits(net, tokens, 5, pages=[9, 4, 17, 6],
                                  slot_row=slot_row, batch=batch, row=row)
    assert np.abs(solo - moved).max() <= TOL[dtype] * np.abs(solo).max()


def test_a_step_leaves_the_other_slots_as_they_were():
    """A step leaves the ring of a slot with no row in the batch as it was,
    bit for bit; padded rows write the trash row only."""
    net, _w = build(tiny_cfg("float32"))
    cache = new_cache(net)
    first = cache.pages.state.first
    marked = list(cache.pools)
    marked[first] = marked[first].at[:, 3].set(0.625)
    marked[first + 1] = marked[first + 1].at[:, 3].set(0.5)
    tokens = np.random.default_rng(2).integers(0, 97, 12)
    _l, _x, pools = decode_logits(net, tokens, 4, pages=[2, 4], slot_row=2,
                                  batch=4, row=1, cache=cache,
                                  pools=tuple(marked))
    assert (np.asarray(pools[first][:, 3]) == 0.625).all()
    assert (np.asarray(pools[first + 1][:, 3]) == 0.5).all()
    assert (np.asarray(pools[first][:, 4]) == 0).all()
    assert np.abs(np.asarray(pools[first][:, 2])).max() > 0
    # one token a step: of the live row's ring only the entries of positions
    # 4..11 (all eight, by now) are written, each with its own token
    ring = np.asarray(pools[first][0, 2])
    assert len({tuple(np.round(r, 5)) for r in ring}) == WINDOW


def test_a_dirty_slot_and_recycled_pages_serve_the_same_logits():
    """Nothing zeroes a slot or a page between owners.  A prompt shorter
    than the window leaves most of its ring unwritten by the commit's
    tokens (the commit stores zeros there, and the step masks what the
    sequence never wrote); the global layers' pages hold another
    sequence's rows behind the prompt.  Junk everywhere serves the same
    logits as zeros, bit for bit."""
    net, _w = build(tiny_cfg("float32"))
    tokens = np.random.default_rng(4).integers(0, 97, 26)
    clean, _x, _p = decode_logits(net, tokens, 3, pages=[1, 2, 5, 6],
                                  slot_row=2, seq_pad=8)
    cache = new_cache(net)
    dirty = tuple(jnp.full(p.shape, 3.0 + j, p.dtype)
                  for j, p in enumerate(cache.pools))
    again, _x, _p = decode_logits(net, tokens, 3, pages=[1, 2, 5, 6],
                                  slot_row=2, seq_pad=8, cache=cache,
                                  pools=dirty)
    np.testing.assert_array_equal(clean, again)
    # and through the allocator: free + alloc hands the same slot out again
    a = cache.alloc(2)
    cache.free(a)
    b = cache.alloc(2)
    assert b.slot_id == a.slot_id and b.generation == a.generation + 1
    assert b.page_table[-1] == b.slot_id + 1 and len(b.page_table) == \
        cache.table_width == MAX_PAGES + 1
    cache.free(b)
    s = cache.stats()
    assert s["pages_in_use"] == 0 and s["state_slots_live"] == 0


def test_the_step_masks_what_the_sequence_never_wrote():
    """Without the commit's zeros either: a ring full of junk that the
    commit never touched (a slot whose commit is skipped) would be read
    only where the step's mask lets it, and at position p < window that is
    entries 0..p."""
    net, _w = build(tiny_cfg("float32"))
    p = net._params_dict(net.param_leaves())
    cache = new_cache(net)
    _pre, _com, step = programs(net, cache.pages)
    table = jnp.asarray(table_row([1, 2], 2)[None])
    outs = []
    for junk in (0.0, 9.0):
        pools = list(cache.pools)
        first = cache.pages.state.first
        for j in (first, first + 1):
            pools[j] = jnp.full(pools[j].shape, junk, pools[j].dtype)
        logits = None
        for t in range(3):              # positions 0, 1, 2: no prefill at all
            logits, pools, _x = step(p, jnp.asarray([7 + t], "int32"),
                                     jnp.asarray([t], "int32"), table,
                                     tuple(pools))
        outs.append(np.asarray(logits))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_padding_rows_are_routed_nowhere_and_counted_out():
    net, _w = build(tiny_cfg("float32"))
    tokens = np.random.default_rng(3).integers(0, 97, 12)
    _l, extras, _p = decode_logits(net, tokens, 4, pages=[2, 4], slot_row=1,
                                   batch=4, row=1)
    moe_rows, live = (np.asarray(e) for e in extras)
    # four expert layers, six held experts and the total; one real row of
    # four: 4 assignments a layer over all 16 experts, not 16
    assert moe_rows.shape == (4, 7) and live.tolist() == [1]
    assert (moe_rows[:, -1] == 4).all()
    assert (moe_rows[:, :-1].sum(1) <= 4).all()


# ------------------------------------------------------ (c) the expert layer
def _parent_route_to_held(x, router_w, held, *, top_k, n_group=1,
                          topk_group=1, scale=1.0, valid=None):
    """``parallel.moe.route_to_held`` as it stood before the selection bias
    became its argument (PR 31), verbatim but for the names it imports."""
    from jax import lax
    T = x.shape[0]
    E = router_w.shape[1]
    G = len(held)
    scores = jax.nn.sigmoid(jnp.dot(
        x, router_w, precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32))
    ids, chosen = moe.group_limited_topk(scores, top_k, n_group, topk_group)
    weights = scale * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    lookup = np.full((E,), G, "int32")
    lookup[np.asarray(held, "int64")] = np.arange(G, dtype="int32")
    local = jnp.asarray(lookup)[ids]              # G = held elsewhere
    if valid is None:
        n_assign = jnp.int32(T * top_k)
    else:
        local = jnp.where(valid[:, None], local, G)
        n_assign = valid.sum().astype(jnp.int32) * top_k
    return local, weights, n_assign


@pytest.mark.parametrize("share", ["routed_expert_share",
                                   "routed_relu2_share"])
def test_without_a_selection_bias_the_shares_lower_the_text_they_had(
        share, monkeypatch):
    """The selection bias is an argument of the shared routing, not a
    switch: left out, ``routed_expert_share`` (A.X-K1's block) and
    ``routed_relu2_share`` (Nemotron's) lower the very text that the
    routing without the argument lowers."""
    from mxnet_tpu.serving.decode.hybrid_moe import routed_relu2_share
    k = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(k[0], (16, 32))
    router = jax.random.normal(k[1], (32, 8))
    wg, wu = (jax.random.normal(kk, (4, 32, 48)) for kk in k[2:4])
    wd = jax.random.normal(k[4], (4, 48, 32))
    kw = dict(top_k=2, n_group=2, topk_group=1, scale=2.5,
              valid=jnp.arange(16) < 11)
    if share == "routed_expert_share":
        def fn(*v):
            return moe.routed_expert_share(*v, (0, 1, 4, 5), **kw)
        args = (x, router, wg, wu, wd)
    else:
        def fn(*v):
            return routed_relu2_share(*v, (0, 1, 4, 5), **kw)
        args = (x, router, wu, wd)

    def text():
        return jax.jit(fn).lower(*args).as_text()

    now = text()
    monkeypatch.setattr(moe, "route_to_held", _parent_route_to_held)
    assert text() == now
    assert "dot_general" in now and "top_k" in now      # it is the routing


def test_the_selection_bias_changes_the_choice_and_not_the_weights():
    """``noaux_tc``: the experts are the largest of ``s + b``; their
    weights are ``s_k / sum_chosen s``, of the scores without ``b``."""
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(k[0], (40, 32))
    router = jax.random.normal(k[1], (32, 16)) * 0.3
    bias = 0.2 * jax.random.normal(k[2], (16,))
    held = tuple(range(16))
    kw = dict(top_k=4, scale=1.0)
    plain, w_plain, _n = moe.route_to_held(x, router, held, **kw)
    biased, w_biased, _n = moe.route_to_held(x, router, held,
                                             select_bias=bias, **kw)
    s = np.asarray(jax.nn.sigmoid(jnp.dot(x, router, precision="highest")))
    want = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :4]
    assert {tuple(sorted(r)) for r in np.asarray(biased).tolist()} == \
        {tuple(sorted(r)) for r in want.tolist()} or \
        (np.sort(np.asarray(biased), -1) == np.sort(want, -1)).all()
    moved = (np.sort(np.asarray(biased), -1)
             != np.sort(np.asarray(plain), -1)).any(-1)
    assert 5 <= moved.sum() < 40        # it moves choices, and not all
    chosen = np.take_along_axis(s, np.asarray(biased), axis=-1)
    np.testing.assert_allclose(
        w_biased, chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    # a bias that cannot change the order changes nothing at all
    same, w_same, _n = moe.route_to_held(
        x, router, held, select_bias=jnp.full((16,), 0.3), **kw)
    np.testing.assert_array_equal(same, plain)
    np.testing.assert_allclose(w_same, w_plain, rtol=1e-6)
    # and one that forces expert 5 on every token still weighs it by its
    # own score
    forced, w_forced, _n = moe.route_to_held(
        x, router, held, select_bias=jnp.zeros((16,)).at[5].set(9.0), **kw)
    assert (np.asarray(forced)[:, 0] == 5).all()
    np.testing.assert_allclose(
        np.asarray(w_forced).sum(-1), 1.0, rtol=1e-5)
    assert np.asarray(w_forced)[:, 0].max() < 0.9


def test_shares_of_a_deployment_add_up_to_the_uncut_layer():
    """Sixteen chips hold sixteen experts each of 256, at a small width:
    the routed parts of all sixteen shares (there is no shared expert to
    count once) are the uncut reference layer, selection bias and all; and
    the shares' rows are the assignments made."""
    full = dict(tiny_cfg("float32", held=tuple(range(256))),
                hidden_size=32, moe_intermediate_size=16,
                num_experts_per_tok=8, selection_bias_std=0.05)
    full["published"] = {"n_routed_experts": 256}
    w = ref.weights(full, seed_key(11, stream=1))
    T = 24
    h = jax.random.normal(jax.random.PRNGKey(2), (T, 32), jnp.float32)
    lw = {k[len("layers.1."):]: v for k, v in w.items()
          if k.startswith("layers.1.")}
    want = ref._moe_mlp(lw, h, cfg_key=ref._freeze(full, 16),
                        precision="float32") - h
    m = window_moe._rms(h, lw["post_attention_layernorm"], 1e-5)
    total, rows_all, assigned, one = 0.0, [], None, None
    for rank in range(16):
        held = tuple(range(16 * rank, 16 * rank + 16))
        ids = np.asarray(held)
        y, rows, n_assign = moe.routed_expert_share(
            m, lw["mlp.gate"], lw["mlp.experts.gate_proj"][ids],
            lw["mlp.experts.up_proj"][ids], lw["mlp.experts.down_proj"][ids],
            held, top_k=8,
            select_bias=lw["mlp.gate.e_score_correction_bias"])
        total, one = total + y, y
        rows_all.append(np.asarray(rows))
        assigned = int(n_assign)
    scale = float(jnp.abs(want).max())
    assert scale > 1e-3
    assert float(jnp.abs(total - want).max()) <= 2e-5 * scale
    assert assigned == T * 8 == int(np.concatenate(rows_all).sum())
    # one share alone is NOT the layer (the test would pass on zeros else)
    assert float(jnp.abs(one - want).max()) > 0.05 * scale
    # the bias moved some of these choices: without it the sum is another
    plain = sum(moe.routed_expert_share(
        m, lw["mlp.gate"],
        *(lw[f"mlp.experts.{n}_proj"][16 * r:16 * r + 16]
          for n in ("gate", "up", "down")),
        tuple(range(16 * r, 16 * r + 16)), top_k=8)[0] for r in range(16))
    assert float(jnp.abs(plain - want).max()) > 1e-3 * scale


# ------------------------------------------------- (d) the attention's parts
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("layer", [0, 1])
def test_blocked_prompt_attention_is_dense_masked_attention(dtype, layer):
    """A global layer (0) and a window layer (1): the blocks of 8 queries
    (over all the keys; over the band of two blocks) against per-head dense
    attention with each K/V head repeated for its query heads, the window's
    mask and the sink as a column of the softmax.  20 positions: two whole
    blocks and a half."""
    net, _w = build(tiny_cfg(dtype))
    p = net._params_dict(net.param_leaves())
    S, B = 20, 2
    a = jax.random.normal(jax.random.PRNGKey(1), (B, S, 64), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    q, k, v = net._qkv(p, layer, a, pos)
    got = np.asarray(jax.jit(
        lambda *x: net.attend_prompt(p, layer, *x))(q, k, v))
    g = 1 if layer == 0 else 2
    qh = np.asarray(q, "float64").reshape(B, S, 4, 24)
    kh = np.repeat(np.asarray(k.astype(jnp.float32), "float64").reshape(
        B, S, g, 24), 4 // g, axis=2)
    vh = np.repeat(np.asarray(v.astype(jnp.float32), "float64").reshape(
        B, S, g, 16), 4 // g, axis=2)
    s = np.einsum("bqhd,bkhd->bhqk", qh, kh) / np.sqrt(24.0)
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    ok = (j <= i) & ((j > i - WINDOW) | (layer == 0))
    s = np.where(ok, s, -np.inf)
    e = np.exp(s - s.max(-1, keepdims=True))
    den = e.sum(-1, keepdims=True)
    if layer == 1:
        sink = np.asarray(p["l1_sink"], "float64")[None, :, None, None]
        den = den + np.exp(sink - s.max(-1, keepdims=True))
    want = np.einsum("bhqk,bkhd->bqhd", e / den, vh).reshape(B, S, 64)
    assert np.abs(got - want).max() <= TOL[dtype] * np.abs(want).max()
    # the sink is not nothing here: it takes a visible share of the mass
    if layer == 1:
        assert (1 - (e / den).sum(-1)).mean() > 0.05


def test_rotary_turns_the_first_third_of_a_head_and_leaves_the_rest():
    net, _w = build(tiny_cfg("float32"))
    assert net.rot_dim == 8 and net.head_dim == 24
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 2, 24))
    pos = jnp.asarray([0, 5, 40])
    for kind, base in ((0, 1e7), (1, 1e4)):
        y = np.asarray(net._rope(x, pos, kind))
        np.testing.assert_array_equal(y[..., 8:], np.asarray(x)[..., 8:])
        np.testing.assert_allclose(y[0], np.asarray(x)[0], rtol=1e-6)
        inv = base ** (-np.arange(0, 8, 2) / 8.0)
        ang = 40 * inv
        x1, x2 = np.asarray(x)[2, :, :4], np.asarray(x)[2, :, 4:8]
        np.testing.assert_allclose(
            y[2, :, :4], x1 * np.cos(ang) - x2 * np.sin(ang), rtol=2e-4,
            atol=2e-5)
        np.testing.assert_allclose(
            y[2, :, 4:8], x2 * np.cos(ang) + x1 * np.sin(ang), rtol=2e-4,
            atol=2e-5)
    # the two kinds turn at different rates past the first pair
    assert np.abs(np.asarray(net._rope(x, pos, 0))[2]
                  - np.asarray(net._rope(x, pos, 1))[2]).max() > 0.1


# ------------------------------------ (e) the cache and runtime read the block
def test_cache_holds_two_kinds_of_attention_state_under_one_allocator():
    """Pages for the two global layers, a ring a slot for the three window
    layers; ``stats()`` reports the bytes of each kind and the live rows; a
    window layer's stored K/V a row does not grow with the context, a
    global layer's does."""
    net, _w = build(tiny_cfg())
    layout = net.cache_layout()
    assert layout["layers"] == 2 and layout["state"]["layers"] == 3
    cache = PagedKVCache(layout=layout, page_size=PAGE, num_pages=9,
                         max_pages_per_seq=4, max_slots=3)
    k, v, ring_k, ring_v = cache.pools
    # 1 K/V head of 24-wide keys over 16-wide values, paged
    assert k.shape == (2, 9, PAGE, 24) and v.shape == (2, 9, PAGE, 16)
    # 2 K/V heads, the last 8 tokens, a slot (and the trash row)
    assert ring_k.shape == (3, 4, WINDOW, 48) and \
        ring_v.shape == (3, 4, WINDOW, 32)
    assert {x.dtype for x in cache.pools} == {jnp.dtype(jnp.bfloat16)}
    assert cache.kv_bytes_per_token == 2 * (24 + 16) * 2
    assert cache.table_width == 5 and cache.prefix_sharing is False
    s = cache.stats()
    assert s["state_slots_live"] == 0
    assert s["state_bytes"] == 4 * 3 * WINDOW * (48 + 32) * 2 == \
        cache.state_bytes
    assert s["page_pool_bytes"] == 9 * PAGE * 2 * (24 + 16) * 2 == \
        cache.page_pool_bytes
    assert net.ring_bytes_per_row * 4 == cache.state_bytes
    slot = cache.alloc(3, prompt=np.arange(9))
    s = cache.stats()
    assert s["state_slots_live"] == 1 and s["pages_in_use"] == 3
    assert s["prefix_skipped"] == 1
    cache.publish(slot, np.arange(9), np.zeros(97))      # a no-op
    assert cache.stats()["prefix_cached_pages"] == 0
    cache.free(slot)
    # four times the context: four times the pages a row may hold, the
    # same ring
    longer = PagedKVCache(
        layout=build(tiny_cfg(), max_length=256)[0].cache_layout(),
        page_size=PAGE, num_pages=33, max_pages_per_seq=16, max_slots=3)
    assert longer.state_bytes == cache.state_bytes
    assert longer.context_length == 4 * cache.context_length
    assert [p.shape[2:] for p in longer.pools[2:]] == \
        [p.shape[2:] for p in cache.pools[2:]]


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
                      seq_buckets=(8,), warm=False, start=False, **kwargs)


def test_mesh_and_bad_patterns_say_so():
    net, _w = build(tiny_cfg(), fresh=True)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("model",))
    with pytest.raises(ValueError, match="slot pools, which are not sharded"):
        PagedKVCache(layout=net.cache_layout(), mesh=mesh)
    with pytest.raises(ValueError, match="slot pools, which are not sharded"):
        DecodeSession(net, page_size=PAGE, batch_buckets=(1,),
                      seq_buckets=(8,), warm=False, start=False, mesh=mesh)
    for bad in ((0, 0, 0), (1, 1), (0, 1, 2)):
        with pytest.raises(ValueError, match="needs a layer of each kind"):
            WindowMoELM(layer_pattern=bad, moe_layer_freq=(0,) * len(bad))
    with pytest.raises(ValueError, match="an entry a layer"):
        WindowMoELM(layer_pattern=(0, 1), moe_layer_freq=(0, 1, 1))
    with pytest.raises(ValueError, match="not divisible by"):
        WindowMoELM(num_attention_heads=5)
    with pytest.raises(ValueError, match="rotate-half pairing needs"):
        WindowMoELM(head_dim=16, partial_rotary_factor=0.2)
    # the system file builds ONE count of query heads and pair of widths
    cfg = dict(tiny_cfg(), swa_head_dim=32)
    with pytest.raises(ValueError, match="swa_head_dim=32 differs"):
        build(cfg)


def test_runtime_sizes_slots_and_tables_from_the_block():
    net, _w = build(tiny_cfg(), max_length=48, fresh=True)
    rt = DecodeRuntime(net, page_size=PAGE, batch_buckets=(1, 4),
                       seq_buckets=(8, 16), warm=False)
    assert rt.cache.context_length == 48 and rt.cache.max_pages_per_seq == 6
    # a ring a row, not two: the state pools are what a slot costs
    assert rt.cache.max_slots == 4 and rt.cache.table_width == 7
    assert rt.prefill_batch_buckets == (1,) == (net.max_prefill_batch,)
    shapes = net.prefill_state(1, 16)
    assert [s for s, _d in shapes] == [(2, 1, 16, 24), (2, 1, 16, 16),
                                       (3, 1, 8, 48), (3, 1, 8, 32)]


# ------------------------------------------------- through the normal path
@pytest.fixture(scope="module")
def session():
    net, w = build(tiny_cfg("float32"), seed=5, fresh=True)
    sess = DecodeSession(net, page_size=PAGE, batch_buckets=(1, 2, 4),
                         seq_buckets=(8, 16, 32))
    yield sess, net, w
    sess.close(drain=False)


def test_session_serves_the_reference_greedy_stream(session):
    """Gateway's session, scheduler, runtime and cache: five requests over
    four slots (so one waits for a slot that another leaves, with that
    sequence's rings in it), prompts under and over the window, each
    decoding past a wrap of its rings, each produce the reference's own
    greedy continuation (float32, so the argmax is the reference's)."""
    sess, net, w = session
    cfg = tiny_cfg("float32")
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, 97, n).tolist() for n in (5, 19, 3, 9, 26)]
    futs = [sess.submit(p, max_new_tokens=12) for p in prompts]
    for prompt, fut in zip(prompts, futs):
        seq = list(prompt)
        for _ in range(12):
            logits = reference_logits(w, cfg, seq, len(seq) - 1)
            seq.append(int(np.argmax(logits[0])))
        assert fut.result(timeout=120).token_ids == seq[len(prompt):]
    s = sess.stats()
    assert s["pages_in_use"] == 0 and s["slots_in_use"] == 0
    assert s["state_slots_live"] == 0 and s["state_bytes"] > 0
    assert s["page_pool_bytes"] > 0
    assert s["prefix_skipped"] >= 5 and s["prefix_hits"] == 0


def test_step_counters_ride_the_fetch(session):
    sess, net, _w = session
    mx.telemetry.enable()
    try:
        mx.telemetry.reset()
        sess.generate(list(range(1, 8)), max_new_tokens=5, timeout=120)
        snap = mx.telemetry.snapshot()
        c = snap["counters"]
    finally:
        mx.telemetry.disable()
    steps = c["decode.steps"]
    assert steps == 4                       # the first token is the prefill's
    # one row, four expert layers, 4 choices each over all 16 experts
    assert c["decode.moe.assignments"] == steps * 4 * 4
    assert c["decode.moe.layer_steps"] == steps * 4
    assert 0 < c["decode.moe.assignments_held"] <= c["decode.moe.assignments"]
    # three window layers a step, one live row's ring each
    assert c["decode.window.layer_steps"] == steps * 3
    assert c["decode.window.ring_rows"] == steps * 3
    assert snap["gauges"]["decode.window.live_rows"] == 1
    assert snap["gauges"]["decode.window.live_bytes"] == \
        net.ring_bytes_per_row == 3 * WINDOW * (48 + 32) * 4
    # prefix sharing was asked for (the default) and skipped, not looked up
    assert c["decode.prefix.skipped"] == 1
    assert "decode.prefix_hits" not in c and "decode.prefix_misses" not in c
    assert snap["gauges"]["decode.state_slots_live"] == 0
    assert snap["gauges"]["decode.state_bytes"] == sess.cache.state_bytes
