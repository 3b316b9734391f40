"""The hybrid state-space / attention / routed-expert decode block against
its plain reference (``perf/reference/nemotron_h.py``), at a small size on
the CPU with seeded weights: prefill (the chunked scan) then decoding through
the paged K/V and the slotted recurrent state against the reference's full
forward pass (the sequential recurrence), batch composition and slot
placement, a freed slot's state, the shares of a small deployment adding up
to the uncut expert layer, the ``relu^2`` expert form against a loop (and
the SwiGLU path as it was), grouped-query attention against per-head
repetition, what the block refuses, and the session's stream and counters.

Tolerances, as a share of the largest logit.  ``float32`` runs every product
at the highest precision, so the program and the reference differ by
summation order and by the chunked scan's rearrangement of the recurrence:
5e-5 (measured 5e-7 to 9e-7 over seeds 3 to 8).  This is the run that ties
the mathematics down.  ``bfloat16`` rounds both inputs of every product to 8
bits of mantissa (2**-9 relative), the K/V rows and the convolution's input
once more; over some six products a layer and seven layers that is about
sqrt(40) * 2**-9 = 1.2% at a real width and more at 64 wide: 5% (measured
1.0% to 3.9% at seed 8, which the tests use).  That holds where the
program's expert choices are the reference's; where two router scores lie
within bfloat16's noise the choice flips and that token's logits move by 10
to 50% of their scale (1 to 14 of 30 positions at seeds 3 to 7 at this
width), which no tolerance on logits covers and none is claimed
(``tests/test_latent_moe.py`` says the same of the other block); the
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
from mxnet_tpu.parallel.moe import routed_expert_share  # noqa: E402
from mxnet_tpu.serving.decode import (DecodeRuntime,  # noqa: E402
                                      DecodeSession, HybridSSMMoELM,
                                      LatentMoELM, PagedKVCache,
                                      get_decode_model)
from mxnet_tpu.serving.decode import hybrid_moe  # noqa: E402
from mxnet_tpu.serving.decode.hybrid_moe import (  # noqa: E402
    routed_relu2_share)
from decode_block_harness import (MAX_PAGES, PAGE, Kit,  # noqa: E402
                                  decode_logits, new_cache, programs)
from perf.harness.weights import seed_key  # noqa: E402
from perf.reference import nemotron_h as ref  # noqa: E402
from perf.systems import hybrid_moe_gateway as system_mod  # noqa: E402

TOL = {"float32": 5e-5, "bfloat16": 5e-2}

# built and compiled once a configuration: tests/decode_block_harness.py
KIT = Kit(ref, system_mod, TOL)
build = KIT.build


def tiny_cfg(dtype="bfloat16", held=(0, 1, 2, 3, 8, 9), pattern="MEM*EME"):
    """The family's keys at a size the CPU runs in a second; 0.2 for the
    initialiser so that the logits are of order 1, and steps large enough
    that a state forgets within the sequences used here."""
    return {"hybrid_override_pattern": pattern, "hidden_size": 64,
            "mamba_num_heads": 8, "mamba_head_dim": 8, "ssm_state_size": 16,
            "n_groups": 2, "conv_kernel": 4, "chunk_size": 8,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "moe_intermediate_size": 32,
            "moe_shared_expert_intermediate_size": 64,
            "n_routed_experts": len(held), "held_experts": list(held),
            "published": {"n_routed_experts": 16}, "num_experts_per_tok": 4,
            "n_group": 1, "topk_group": 1, "routed_scaling_factor": 2.5,
            "norm_eps": 1e-5, "vocab_size": 97, "initializer_range": 0.2,
            "time_step_min": 0.01, "time_step_max": 0.5,
            "time_step_floor": 1e-4, "precision": {"weights": dtype}}


# ------------------------------------------------- (a) against the reference
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n_prompt,seq_pad", [(1, 8), (9, 16), (13, 24)])
def test_prefill_then_decode_matches_reference(dtype, n_prompt, seq_pad):
    """The chunked scan over a padded prompt (true lengths that are not
    whole chunks of 8), its state and tail handed to a slot, then single
    steps: the reference's full forward over the whole sequence."""
    cfg = tiny_cfg(dtype)
    net, w = build(cfg)
    tokens = np.random.default_rng(7).integers(0, 97, 30)
    got, _x, _p = decode_logits(net, tokens, n_prompt, pages=[3, 5, 7, 9],
                                slot_row=2, seq_pad=seq_pad)
    want = np.asarray(ref.forward(w, cfg, jnp.asarray(tokens)))[n_prompt - 1:]
    scale = np.abs(want).max()
    assert scale > 0.5          # logits of order 1, not a comparison of zeros
    assert np.abs(got - want).max() <= TOL[dtype] * scale


def test_float32_is_much_tighter_than_bfloat16():
    tokens = np.random.default_rng(8).integers(0, 97, 24)
    err = {}
    for dtype in ("float32", "bfloat16"):
        cfg = tiny_cfg(dtype)
        net, w = build(cfg)
        got, _x, _p = decode_logits(net, tokens, 6, pages=[1, 2, 3],
                                    slot_row=1)
        want = np.asarray(ref.forward(w, cfg, jnp.asarray(tokens)))[5:]
        err[dtype] = np.abs(got - want).max() / np.abs(want).max()
    assert err["float32"] < 5e-5 < 1e-3 < err["bfloat16"] < 5e-2


def test_prefill_hands_over_state_as_of_the_true_length():
    """The same prompt under three paddings: the recurrent state and the
    convolution's tail that prefill emits do not depend on the bucket."""
    net, _w = build(tiny_cfg("float32"))
    p = net._params_dict(net.param_leaves())
    tokens = np.random.default_rng(5).integers(0, 97, 11)
    prefill = programs(net, new_cache(net).pages)[0]
    got = []
    for pad in (16, 24, 32):
        prompt = np.full((1, pad), 96, "int32")       # junk behind the prompt
        prompt[0, :11] = tokens
        _l, _k, _v, ssm_state, tail = prefill(
            p, jnp.asarray(prompt), jnp.asarray([11], "int32"))
        got.append((np.asarray(ssm_state), np.asarray(tail)))
    for ssm_state, tail in got[1:]:
        np.testing.assert_allclose(ssm_state, got[0][0], rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_array_equal(tail, got[0][1])
    assert np.abs(got[0][0]).max() > 1e-3


# --------------------------------------- (b) batch, pages and slot placement
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("batch,row,slot_row", [(4, 2, 3), (2, 0, 4)])
def test_batch_composition_and_slot_placement_do_not_change_a_row(
        dtype, batch, row, slot_row):
    """Solo in a one-row program against a row of a padded batch (of 4 and
    of 2 rows, the live row not the first) in other pages and another slot:
    the step finds a row's state by its state row, wherever the row stands
    (here the CPU's form, read / ``ssm_step`` / write; the chip's kernel is
    held to the same in ``tests/test_ssm_step_kernel.py``)."""
    net, _w = build(tiny_cfg(dtype))
    tokens = np.random.default_rng(9).integers(0, 97, 20)
    solo, _x, _p = decode_logits(net, tokens, 5, pages=[1, 2, 3], slot_row=1)
    moved, _x, _p = decode_logits(net, tokens, 5, pages=[9, 4, 17],
                                  slot_row=slot_row, batch=batch, row=row)
    assert np.abs(solo - moved).max() <= TOL[dtype] * np.abs(solo).max()


def test_a_step_leaves_the_other_slots_as_they_were():
    """A step leaves a slot with no row in the batch as it was, bit for
    bit; padded rows write the trash row only (the chip's kernel writes not
    even that: ``tests/test_ssm_step_kernel.py``)."""
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


def test_a_freed_slots_state_does_not_reach_its_next_owner():
    """Nothing zeroes a slot between owners; the next owner's commit
    overwrites its state whole.  A slot left full of another sequence's
    state (here: of junk) serves the same logits as a clean one."""
    net, _w = build(tiny_cfg("float32"))
    tokens = np.random.default_rng(4).integers(0, 97, 16)
    clean, _x, _p = decode_logits(net, tokens, 6, pages=[1, 2], slot_row=2)
    cache = new_cache(net)
    first = cache.pages.state.first
    dirty = list(cache.pools)
    dirty[first] = dirty[first].at[:, 2].set(1e3)
    dirty[first + 1] = dirty[first + 1].at[:, 2].set(-7.0)
    again, _x, _p = decode_logits(net, tokens, 6, pages=[1, 2], slot_row=2,
                                  cache=cache, pools=tuple(dirty))
    np.testing.assert_array_equal(clean, again)
    # and through the allocator: free + alloc hands the same slot out again
    a = cache.alloc(2)
    cache.free(a)
    b = cache.alloc(2)
    assert b.slot_id == a.slot_id and b.generation == a.generation + 1
    assert b.page_table[-1] == b.slot_id + 1 and len(b.page_table) == \
        cache.table_width == MAX_PAGES + 1
    cache.free(b)


def test_padding_rows_are_routed_nowhere_and_counted_out():
    net, _w = build(tiny_cfg("float32"))
    tokens = np.random.default_rng(3).integers(0, 97, 12)
    _l, extras, _p = decode_logits(net, tokens, 4, pages=[2, 4], slot_row=1,
                                   batch=4, row=1)
    moe_rows, live = (np.asarray(e) for e in extras)
    # three expert layers, six held experts and the total; one real row of
    # four: 4 assignments a layer over all 16 experts, not 16
    assert moe_rows.shape == (3, 7) and live.tolist() == [1]
    assert (moe_rows[:, -1] == 4).all()
    assert (moe_rows[:, :-1].sum(1) <= 4).all()


# ------------------------------------------------------ (c) the expert layer
def test_shares_of_a_deployment_add_up_to_the_uncut_layer():
    """Four chips hold four experts each of 16: the routed parts of all the
    shares, with the shared expert counted once, are the uncut reference
    layer; and a share's rows and assignments are counted right."""
    full = tiny_cfg("float32", held=tuple(range(16)), pattern="M*E")
    w = ref.weights(full, seed_key(11, stream=1))
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 40, 64), jnp.float32)
    lw = {k[len("layers.2."):]: v for k, v in w.items()
          if k.startswith("layers.2.")}
    want = (ref._experts(lw, h, cfg_key=ref._freeze(full),
                         precision="float32") - h)[0]
    m = hybrid_moe._rms(h[0], lw["norm"], 1e-5)
    shared = hybrid_moe._relu2(m, lw["mixer.shared_experts.up_proj"],
                               lw["mixer.shared_experts.down_proj"])
    total, rows_all, assigned = shared, [], None
    for rank in range(4):
        held = tuple(range(4 * rank, 4 * rank + 4))
        ids = np.asarray(held)
        y, rows, n_assign = routed_relu2_share(
            m, lw["mixer.gate"], lw["mixer.experts.up_proj"][ids],
            lw["mixer.experts.down_proj"][ids], held, top_k=4, scale=2.5)
        total = total + y
        rows_all.append(np.asarray(rows))
        assigned = int(n_assign)
    assert float(jnp.abs(total - want).max()) <= 2e-5 * float(
        jnp.abs(want).max())
    assert assigned == 40 * 4 == int(np.concatenate(rows_all).sum())
    # one share alone is NOT the layer (the test would pass on zeros else)
    assert float(jnp.abs(shared + y - want).max()) > 0.05 * float(
        jnp.abs(want).max())


def test_relu2_share_is_the_loop_over_experts():
    k = jax.random.split(jax.random.PRNGKey(6), 4)
    x = jax.random.normal(k[0], (24, 32))
    router = jax.random.normal(k[1], (32, 8))
    wu = jax.random.normal(k[2], (8, 32, 48)) * 0.2
    wd = jax.random.normal(k[3], (8, 48, 32)) * 0.2
    # (compiled: op by op every conditional is traced and compiled anew)
    y, rows, n = jax.jit(lambda *v: routed_relu2_share(
        *v, tuple(range(8)), top_k=3, scale=1.5))(x, router, wu, wd)
    s = jax.nn.sigmoid(jnp.dot(x, router, precision="highest"))
    top = np.argsort(-np.asarray(s), axis=-1)[:, :3]
    want = np.zeros((24, 32))
    for t in range(24):
        chosen = np.asarray(s)[t, top[t]]
        for e, sc in zip(top[t], chosen):
            hid = np.maximum(np.asarray(x[t], "float64")
                             @ np.asarray(wu[e], "float64"), 0.0) ** 2
            want[t] += 1.5 * sc / chosen.sum() * (
                hid @ np.asarray(wd[e], "float64"))
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-4)
    assert int(n) == 72 == int(rows.sum())


@pytest.mark.parametrize("real", [13, 1])
def test_padding_is_routed_nowhere_and_an_unchosen_expert_is_skipped(real):
    """Of 20 rows ``real`` are real: the others reach no expert and read
    0, the counts are the real rows', and with one real row most held
    experts receive none: their conditional is skipped and the sum does not
    miss them (the row reads what it reads among 13)."""
    k = jax.random.split(jax.random.PRNGKey(4), 4)
    x = jax.random.normal(k[0], (20, 32))
    router = jax.random.normal(k[1], (32, 16))
    wu = jax.random.normal(k[2], (5, 32, 48)) * 0.2
    wd = jax.random.normal(k[3], (5, 48, 32)) * 0.2
    held = (0, 3, 4, 9, 15)
    kw = dict(top_k=3, scale=2.5)
    # (compiled: op by op every conditional is traced and compiled anew)
    y0, _r, _n = jax.jit(lambda: routed_relu2_share(
        x, router, wu, wd, held, **kw))()
    y, rows, n = jax.jit(lambda valid: routed_relu2_share(
        x, router, wu, wd, held, valid=valid, **kw))(jnp.arange(20) < real)
    np.testing.assert_allclose(y[:real], y0[:real], rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(y[real:]).max()) == 0.0
    assert int(n) == 3 * real and int(rows.sum()) <= 3 * real
    if real == 1:
        assert int((rows == 0).sum()) >= 2


def test_the_two_shares_issue_their_products_their_own_way():
    """The shared layer's gated share is three grouped products and knows
    of nothing else (``LatentMoELM``'s programs lower to the text they had:
    ``PERF.md``, PR 30); this block's un-gated share has none: a
    conditional a held expert."""
    k = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(k[0], (16, 32))
    router = jax.random.normal(k[1], (32, 8))
    wg, wu = (jax.random.normal(kk, (4, 32, 48)) for kk in k[2:4])
    wd = jax.random.normal(k[4], (4, 48, 32))
    kw = dict(top_k=2, n_group=2, topk_group=1, scale=2.5)

    def count(jaxpr, name):
        n = 0
        for eqn in jaxpr.eqns:
            n += eqn.primitive.name.startswith(name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += count(sub, name)
        return n

    gated = jax.make_jaxpr(lambda *v: routed_expert_share(
        *v, (0, 1, 4, 5), **kw))(x, router, wg, wu, wd).jaxpr
    assert count(gated, "ragged_dot") == 3 and count(gated, "cond") == 0
    ungated = jax.make_jaxpr(lambda *v: routed_relu2_share(
        *v, (0, 1, 4, 5), **kw))(x, router, wu, wd).jaxpr
    assert count(ungated, "ragged_dot") == 0 and count(ungated, "cond") == 4


def test_expert_width_is_stored_in_whole_lane_tiles():
    """32 wide is stored 128 wide with zeros behind: ``stored`` pads a
    checkpoint's tensors, nothing else, and the padding adds nothing (the
    comparisons above run through it)."""
    net, w = build(tiny_cfg("float32"))
    assert net.expert_width == 32
    p = net._params_dict(net.param_leaves())
    assert p["l1_exp_wu"].shape == (6, 64, 128)
    assert p["l1_exp_wd"].shape == (6, 128, 64)
    assert float(jnp.abs(p["l1_exp_wu"][:, :, 32:]).max()) == 0.0
    assert float(jnp.abs(p["l1_exp_wd"][:, 32:]).max()) == 0.0
    np.testing.assert_array_equal(
        p["l1_exp_wu"][:, :, :32], w["layers.1.mixer.experts.up_proj"])
    same = w["layers.0.mixer.in_proj"]
    assert net.stored("l0_w_in", same) is same


# ------------------------------------------------- (d) grouped-query heads
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_grouped_query_attention_is_per_head_repetition(dtype):
    """Four query heads over two KV heads: the block's grouped contraction
    against plain multi-head attention with each KV head repeated for its
    two query heads (head j reads KV head j // 2)."""
    net, _w = build(tiny_cfg(dtype))
    p = net._params_dict(net.param_leaves())
    S, layer = 12, 3
    a = jax.random.normal(jax.random.PRNGKey(1), (2, S, 64), jnp.float32)
    q, k, v = net._qkv(p, layer, a)
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool)), (2, S, S))
    got = net.attend(p, layer, q, k, v, causal)
    qh = np.asarray(q, "float64").reshape(2, S, 4, 16)
    kh = np.repeat(np.asarray(k.astype(jnp.float32), "float64").reshape(
        2, S, 2, 16), 2, axis=2)
    vh = np.repeat(np.asarray(v.astype(jnp.float32), "float64").reshape(
        2, S, 2, 16), 2, axis=2)
    s = np.einsum("bqhd,bkhd->bhqk", qh, kh) / 4.0
    s = np.where(np.tril(np.ones((S, S), bool)), s, -np.inf)
    pr = np.exp(s - s.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    o = np.einsum("bhqk,bkhd->bqhd", pr, vh).reshape(2, S, 64)
    want = o @ np.asarray(p[f"l{layer}_wo"].astype(jnp.float32), "float64")
    assert np.abs(np.asarray(got) - want).max() <= \
        TOL[dtype] * np.abs(want).max()


# ------------------------------------ (e) the cache and runtime read the block
def test_cache_builds_paged_and_slot_pools_from_the_layout():
    net, _w = build(tiny_cfg())
    layout = net.cache_layout()
    assert layout["layers"] == 1 and layout["state"]["layers"] == 3
    cache = PagedKVCache(layout=layout, page_size=PAGE, num_pages=5,
                         max_pages_per_seq=2, max_slots=3)
    k, v, ssm_pool, conv_pool = cache.pools
    assert k.shape == v.shape == (1, 5, PAGE, 32) and k.dtype == jnp.bfloat16
    assert ssm_pool.shape == (3, 4, 8, 8, 16) and \
        ssm_pool.dtype == jnp.float32
    # the tail, 3 inputs of 8 * 8 + 2 * 2 * 16 = 128 values, as lane tiles
    assert conv_pool.shape == (3, 4, 3, 128) and \
        conv_pool.dtype == jnp.bfloat16
    assert cache.kv_bytes_per_token == 1 * 2 * 32 * 2
    assert cache.table_width == 3 and cache.prefix_sharing is False
    s = cache.stats()
    assert s["state_slots_live"] == 0
    assert s["state_bytes"] == 4 * 3 * (8 * 8 * 16 * 4 + 384 * 2) == \
        cache.state_bytes
    slot = cache.alloc(1, prompt=np.arange(9))
    assert cache.stats()["state_slots_live"] == 1
    assert cache.stats()["prefix_skipped"] == 1
    cache.publish(slot, np.arange(9), np.zeros(97))      # a no-op
    assert cache.stats()["prefix_cached_pages"] == 0
    cache.free(slot)
    # the blocks without per-sequence state: no state section, no state
    # keys, tables as wide as a page table, prefix sharing as asked
    lm = get_decode_model("decode_tiny", vocab_size=50, max_length=32)
    two = PagedKVCache(layout=lm.cache_layout(), page_size=PAGE, num_pages=5,
                       max_pages_per_seq=2)
    assert two.state is None and two.table_width == 2 and two.prefix_sharing
    assert len(two.pools) == 2 and two.state_bytes == 0
    assert not {"state_slots_live", "state_bytes"} & set(two.stats())
    assert two.pages.addresses("tables") == ("tables", None)
    assert len(two.alloc(1).page_table) == 2
    assert "state" not in LatentMoELM().cache_layout()


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
    for bad in ("MEE", "E*", "MX*"):
        with pytest.raises(ValueError, match="at least one 'M' and one"):
            HybridSSMMoELM(pattern=bad)
    with pytest.raises(ValueError, match="not divisible by"):
        HybridSSMMoELM(num_attention_heads=5)


def test_runtime_sizes_slots_and_tables_from_the_block():
    net, _w = build(tiny_cfg(), max_length=48, fresh=True)
    rt = DecodeRuntime(net, page_size=PAGE, batch_buckets=(1, 4),
                       seq_buckets=(8, 16), warm=False)
    assert rt.cache.context_length == 48 and rt.cache.max_pages_per_seq == 6
    # a slot of state a row, not two: the state pools are what a slot costs
    assert rt.cache.max_slots == 4 and rt.cache.table_width == 7
    assert rt.prefill_batch_buckets == (1,) == (net.max_prefill_batch,)
    shapes = net.prefill_state(1, 16)
    assert [s for s, _d in shapes] == [(1, 1, 16, 32), (1, 1, 16, 32),
                                       (3, 1, 8, 8, 16), (3, 1, 3, 128)]
    # CausalLM's default stays two slots a row
    lm = get_decode_model("decode_tiny", vocab_size=50, max_length=32)
    lm.initialize()
    rt2 = DecodeRuntime(lm, page_size=PAGE, batch_buckets=(1, 2),
                        seq_buckets=(8,), warm=False)
    assert rt2.cache.max_slots == 4 and rt2.cache.table_width == 4


# ------------------------------------------------- through the normal path
@pytest.fixture(scope="module")
def session():
    net, w = build(tiny_cfg("float32"), seed=5, fresh=True)
    sess = DecodeSession(net, page_size=PAGE, batch_buckets=(1, 2, 4),
                         seq_buckets=(8, 16))
    yield sess, net, w
    sess.close(drain=False)


def test_session_serves_the_reference_greedy_stream(session):
    """Gateway's session, scheduler, runtime and cache: five requests over
    four slots (so one waits for a slot that another leaves, with that
    sequence's state in it) each produce the reference's own greedy
    continuation (float32, so the argmax is the reference's)."""
    sess, net, w = session
    cfg = tiny_cfg("float32")
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, 97, n).tolist() for n in (5, 11, 3, 9, 14)]
    futs = [sess.submit(p, max_new_tokens=7) for p in prompts]
    for prompt, fut in zip(prompts, futs):
        seq = list(prompt)
        for _ in range(7):
            # padded to one length (every layer is causal: what follows a
            # position does not reach it), so the reference compiles once
            padded = np.zeros((24,), "int32")
            padded[:len(seq)] = seq
            logits = ref.forward(w, cfg, jnp.asarray(padded))
            seq.append(int(jnp.argmax(logits[len(seq) - 1])))
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
        sess.generate(list(range(1, 8)), max_new_tokens=5, timeout=120)
        snap = mx.telemetry.snapshot()
        c = snap["counters"]
    finally:
        mx.telemetry.disable()
    steps = c["decode.steps"]
    assert steps == 4                       # the first token is the prefill's
    # one row, three expert layers, 4 choices each over all 16 experts
    assert c["decode.moe.assignments"] == steps * 3 * 4
    assert c["decode.moe.layer_steps"] == steps * 3
    assert 0 < c["decode.moe.assignments_held"] <= c["decode.moe.assignments"]
    # three Mamba layers a step, one live row's state each
    assert c["decode.ssm.layer_steps"] == steps * 3
    assert c["decode.ssm.state_rows"] == steps * 3
    # prefix sharing was asked for (the default) and skipped, not looked up
    assert c["decode.prefix.skipped"] == 1
    assert "decode.prefix_hits" not in c and "decode.prefix_misses" not in c
    assert snap["gauges"]["decode.state_slots_live"] == 0
    assert snap["gauges"]["decode.state_bytes"] == sess.cache.state_bytes
