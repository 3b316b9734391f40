"""The gated delta rule (``mxnet_tpu.ops.delta_rule``), its slots kernel and
the linear-attention / gated grouped-query attention / routed-expert decode
block against their definitions and the plain reference
(``perf/reference/solar_open2.py``), at a small size on the CPU with seeded
weights: the chunked form against the sequential recurrence, the one-token
step continuing a prefill's state, ``kda_step_slots`` under the interpreter,
prefill then decoding through the paged K/V and the slotted matrix state
against the reference's full forward pass, a freed slot's state, the shares
of a deployment adding up to the uncut expert layer, what the block refuses,
and the session's stream and counters.

Tolerances, as a share of the largest logit.  ``float32`` runs every product
at the highest precision, so the program and the reference differ by
summation order and by the chunked form's rearrangement of the recurrence
(a triangular solve a chunk): 5e-5 (measured 3e-6 to 4e-6 at seed 8).  This
is the run that ties the mathematics down: a matrix state rounded to
bfloat16 after every token reads 3e-3 and more, and weights through e4m3
20% (both are tested to fail it).  ``bfloat16`` rounds both inputs of every
product to 8 bits of mantissa (2**-9 relative), the K/V rows and the
convolutions' input once more; over some eight products a layer and five
layers that is about sqrt(40) * 2**-9 = 1.2% at a real width and more at 64
wide: 6% (measured 1.5% to 4.2% over seeds 3 to 8).  That holds where the
program's expert choices are the reference's, so the bfloat16 comparison is
made with every expert chosen (``num_experts_per_tok`` = the experts there
are: the weights still differ by token, no choice can flip); with 4 of 16
chosen at this width two router scores lie within bfloat16's noise at 1 to
5 of 30 positions and those tokens' logits move by 10 to 20% of their scale,
which no tolerance on logits covers and none is claimed
(``tests/test_hybrid_moe_lm.py`` says the same of its block); the router's
scores are float32 so that it is rare at the real width, and the benchmark
counts the served tokens it moves.
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
from mxnet_tpu.ops import delta_rule as dr  # noqa: E402
from mxnet_tpu.ops.pallas_kernels import kda_step_slots  # noqa: E402
from mxnet_tpu.parallel.moe import routed_expert_share  # noqa: E402
from mxnet_tpu.serving.decode import (DecodeRuntime,  # noqa: E402
                                      DecodeSession, LinearMoELM,
                                      PagedKVCache)
from mxnet_tpu.serving.decode import linear_moe  # noqa: E402
from mxnet_tpu.test_utils import counted  # noqa: E402
from decode_block_harness import (MAX_PAGES, PAGE, Kit,  # noqa: E402
                                  decode_logits, new_cache, programs)
from perf.harness.weights import seed_key  # noqa: E402
from perf.reference import solar_open2 as ref  # noqa: E402
from perf.systems import linear_moe_gateway as system_mod  # noqa: E402

TOL = {"float32": 5e-5, "bfloat16": 6e-2}

# built and compiled once a configuration: tests/decode_block_harness.py
KIT = Kit(ref, system_mod, TOL)
build = KIT.build


# ------------------------------------------------ (a) the three forms agree
def draw(seed, b, L, H, dk, dv, decay=1.0):
    """Seeded inputs of the recurrence as the mixer makes them: unit keys,
    scaled unit queries, ``g <= 0`` of order ``decay``, ``beta`` in (0,
    2)."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    return (unit(jax.random.normal(k[0], (b, L, H, dk))) * dk ** -0.5,
            unit(jax.random.normal(k[1], (b, L, H, dk))),
            jax.random.normal(k[2], (b, L, H, dv)),
            -decay * jax.nn.softplus(jax.random.normal(k[3], (b, L, H, dk))),
            2 * jax.nn.sigmoid(jax.random.normal(k[4], (b, L, H))))


@pytest.mark.parametrize("L,chunk,sub,decay", [
    (37, 8, 4, 0.3),      # five chunks, the last not whole
    (64, 16, 4, 1.0),     # four whole chunks
    (50, 64, 16, 0.1),    # one chunk longer than the sequence
    (7, 64, 16, 1.0),     # shorter than a sub-chunk
    # a channel forgets up to 60 nats a token, 2,000 a chunk: exp(-G_j)
    # alone is inf from the second token on
    (96, 32, 8, 40.0)])
def test_chunked_form_is_the_sequential_recurrence(L, chunk, sub, decay):
    args = draw(L, 2, L, 3, 16, 8, decay)
    want_o, want_s = dr.delta_rule_sequential(*args)
    got_o, got_s = jax.jit(lambda *a: dr.delta_rule_chunked(
        *a, chunk=chunk, sub=sub))(*args)
    assert bool(jnp.isfinite(got_o).all() & jnp.isfinite(got_s).all())
    # float32 products at the highest precision: the two differ by the
    # order of summation and the solve, a few ulp of the largest value
    assert float(jnp.abs(got_o - want_o).max()) <= 1e-5 * float(
        jnp.abs(want_o).max())
    assert float(jnp.abs(got_s - want_s).max()) <= 1e-5 * float(
        jnp.abs(want_s).max())
    if decay == 40.0:
        G = jnp.cumsum(args[3].reshape(2, 3, 32, 3, 16), axis=2)
        assert not bool(jnp.isfinite(jnp.exp(-G)).all())


def test_padding_neither_decays_the_state_nor_feeds_it():
    """``beta`` = ``g`` = 0 behind position 21: the state handed over is the
    state after 21 tokens, whatever junk lies behind and however long the
    padding."""
    q, k, v, g, beta = draw(4, 1, 40, 2, 16, 16)
    real = (jnp.arange(40) < 21)[None, :, None]
    g, beta = jnp.where(real[..., None], g, 0.0), jnp.where(real, beta, 0.0)
    _o, want = dr.delta_rule_sequential(*(x[:, :21] for x in (q, k, v, g,
                                                              beta)))
    for L in (24, 40):
        _o, got = dr.delta_rule_chunked(*(x[:, :L] for x in (q, k, v, g,
                                                             beta)), chunk=8)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_the_step_continues_a_prefills_state():
    """The chunked form over the first 19 tokens, then one-token steps on
    the state it hands over: the definition over all 30."""
    args = draw(9, 2, 30, 3, 16, 16)
    want_o, want_s = dr.delta_rule_sequential(*args)
    o, state = dr.delta_rule_chunked(*(x[:, :19] for x in args), chunk=8)
    out = [o]
    for t in range(19, 30):
        state, o_t = dr.delta_rule_step(state, *(x[:, t] for x in args))
        out.append(o_t[:, None])
    np.testing.assert_allclose(jnp.concatenate(out, 1), want_o, rtol=2e-5,
                               atol=2e-6)
    np.testing.assert_allclose(state, want_s, rtol=2e-5, atol=2e-6)


def test_bfloat16_products_stay_near_and_a_bfloat16_state_does_not():
    """The in-chunk products in bfloat16 (float32 accumulation, ``G``, ``A``
    and ``T`` float32) move the output by under 2% of its scale; what the
    float32 tolerance of the block is there to refuse, a state rounded to
    bfloat16 after every token, moves it by more than 0.1%."""
    args = draw(11, 1, 48, 2, 32, 32, 0.2)
    want, _s = dr.delta_rule_sequential(*args)
    scale = float(jnp.abs(want).max())
    got, _s = dr.delta_rule_chunked(*args, chunk=16, sub=8, dtype="bfloat16")
    assert 1e-4 * scale < float(jnp.abs(got - want).max()) < 2e-2 * scale
    state, out = jnp.zeros((1, 2, 32, 32)), []
    for t in range(48):
        state, o = dr.delta_rule_step(state, *(x[:, t] for x in args))
        state = state.astype(jnp.bfloat16).astype(jnp.float32)
        out.append(o[:, None])
    assert float(jnp.abs(jnp.concatenate(out, 1) - want).max()) > \
        1e-3 * scale


# ------------------------------------------------- (b) the kernel on slots
@pytest.mark.parametrize("rows", [[2, 0, 5, 1], [0, 0, 3, 0], [0, 0, 0, 0],
                                  [4, 3, 2, 1]])
def test_kda_step_slots_is_the_step_on_the_named_rows(rows):
    """Under the interpreter: each live row's state of the layer is the
    definition's, found by its state row wherever the row stands; padded
    rows (state row 0) move nothing and read zeros; the trash row, the
    state rows no batch row names and the other layers keep their bits."""
    layers, slots, H, dk, dv = 3, 6, 4, 16, 128
    pool = jax.random.normal(jax.random.PRNGKey(0), (layers, slots, H, dk,
                                                     dv))
    rows = jnp.asarray(rows, jnp.int32)
    b = rows.shape[0]
    q, k, v, g, beta = (x[:, 0] for x in draw(3, b, 1, H, dk, dv))
    new, o = jax.jit(lambda pool, *a: kda_step_slots(
        pool, 1, rows, *a, interpret=True))(pool, q, k, v, g, beta)
    want_s, want_o = dr.delta_rule_step(pool[1, rows], q, k, v, g, beta)
    live = np.asarray(rows) != 0
    want = np.asarray(pool).copy()
    for i, r in enumerate(np.asarray(rows)):
        if r:
            want[1, r] = np.asarray(want_s[i])
    touched = np.zeros(want.shape[:2], bool)
    touched[1, np.asarray(rows)[live]] = True
    np.testing.assert_array_equal(np.asarray(new)[~touched], want[~touched])
    np.testing.assert_allclose(np.asarray(new)[touched], want[touched],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(want_o)[live],
                               rtol=1e-5, atol=2e-6)
    assert (np.asarray(o)[~live] == 0).all()


# ------------------------------------------------------ the block, tiny
def tiny_cfg(dtype="bfloat16", held=(0, 1, 2, 3, 8, 9), published=16,
             top_k=4, n_layer=5):
    """The family's keys at a size the CPU runs in a second; 0.2 for the
    initialiser so that the logits are of order 1, and steps large enough
    that a state forgets within the sequences used here.  Heads of 32: the
    three tails of a slot fill whole 128-lane tiles (3 x 3 x 128)."""
    return {"hidden_size": 64, "n_layer": n_layer, "gqa_layers": [0, 4, 8],
            "linear_attn_config": {"short_conv_kernel_size": 4,
                                   "head_dim": 32, "num_heads": 4,
                                   "num_kv_heads": None},
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "kda_gate_rank": 16, "kda_chunk_size": 8,
            "moe_intermediate_size": 32, "n_routed_experts": len(held),
            "held_experts": list(held),
            "published": {"n_routed_experts": published},
            "num_experts_per_tok": top_k, "routed_scaling_factor": 1,
            "rms_norm_eps": 1e-5, "vocab_size": 97, "initializer_range": 0.2,
            "time_step_min": 0.01, "time_step_max": 0.5,
            "time_step_floor": 1e-4, "precision": {"weights": dtype}}


def all_chosen(dtype):
    """Four experts, all held and all chosen by every token: the expert
    sublayer with no choice to flip (module docstring)."""
    return tiny_cfg(dtype, held=(0, 1, 2, 3), published=4, top_k=4)


def reference_logits(w, cfg, tokens, precision="float32"):
    """The reference's full forward over ``tokens`` padded to 32 (every
    layer is causal), so that it compiles once."""
    padded = np.zeros((32,), "int32")
    padded[:len(tokens)] = tokens
    return np.asarray(ref.forward(w, cfg, jnp.asarray(padded), precision,
                                  query_block=32))[:len(tokens)]


@pytest.fixture(scope="module")
def f32():
    cfg = tiny_cfg("float32")
    return (cfg,) + build(cfg)


# ------------------------------------------------- (c) against the reference
@pytest.mark.parametrize("n_prompt,seq_pad", [(1, 8), (9, 16), (13, 24)])
def test_prefill_then_decode_matches_reference(f32, n_prompt, seq_pad):
    """The chunked form over a padded prompt (true lengths that are not
    whole chunks of 8), its state and tails handed to a slot, the K/V rows
    to pages, then single steps: the reference's full forward (the
    sequential recurrence, dense attention) over the whole sequence, on
    logits."""
    cfg, net, w = f32
    tokens = np.random.default_rng(7).integers(0, 97, 30)
    got, _x, _p = decode_logits(net, tokens, n_prompt, pages=[3, 5, 7, 9],
                                slot_row=2, seq_pad=seq_pad)
    want = reference_logits(w, cfg, tokens)[n_prompt - 1:]
    scale = np.abs(want).max()
    assert scale > 0.5          # logits of order 1, not a comparison of zeros
    assert np.abs(got - want).max() <= TOL["float32"] * scale


def test_bfloat16_is_near_and_float32_much_tighter(f32):
    cfg = all_chosen("bfloat16")
    net, w = build(cfg)
    tokens = np.random.default_rng(8).integers(0, 97, 24)
    got, _x, _p = decode_logits(net, tokens, 6, pages=[1, 2, 3], slot_row=1)
    want = reference_logits(w, cfg, tokens)[5:]
    err = np.abs(got - want).max() / np.abs(want).max()
    assert 1e-3 < err < TOL["bfloat16"]
    # what neither tolerance lets through: every matrix through e4m3
    coarse = reference_logits(w, cfg, tokens, "weights_fp8")[5:]
    assert np.abs(coarse - want).max() > 1.5 * TOL["bfloat16"] * np.abs(
        want).max()
    # nor, on the float32 run, either broken mechanism
    cfg32, _net, w32 = f32
    sound = reference_logits(w32, cfg32, tokens)
    for broken in ("decay_off", "neg_eig_off"):
        moved = reference_logits(w32, cfg32, tokens, broken)
        assert np.abs(moved - sound).max() > 0.05 * np.abs(sound).max()


def test_prompt_attention_by_query_blocks_is_the_whole_attention(
        monkeypatch):
    """Blocks of 8 queries over 20 keys (the last block padded) against all
    20 queries at once under the causal mask."""
    net, _w = build(tiny_cfg("float32"), fresh=True)
    p = net._params_dict(net.param_leaves())
    a = jax.random.normal(jax.random.PRNGKey(1), (1, 20, 64), jnp.float32)
    q, k, v = net._qkv(p, 0, a)
    want = net.attend_heads(q, k, v, jnp.tril(jnp.ones((20, 20), bool))[None])
    monkeypatch.setattr(net, "attention_block", 8)
    np.testing.assert_allclose(net.attend_prompt(q, k, v), want, rtol=1e-5,
                               atol=1e-6)


def test_prefill_hands_over_state_as_of_the_true_length(f32):
    """The same prompt under three paddings: the matrix state and the tails
    that prefill emits do not depend on the bucket."""
    _cfg, net, _w = f32
    p = net._params_dict(net.param_leaves())
    tokens = np.random.default_rng(5).integers(0, 97, 11)
    prefill = programs(net, new_cache(net).pages)[0]
    got = []
    for pad in (16, 24, 32):
        prompt = np.full((1, pad), 96, "int32")       # junk behind the prompt
        prompt[0, :11] = tokens
        _l, _k, _v, state, tail = prefill(
            p, jnp.asarray(prompt), jnp.asarray([11], "int32"))
        got.append((np.asarray(state), np.asarray(tail)))
    for state, tail in got[1:]:
        np.testing.assert_allclose(state, got[0][0], rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(tail, got[0][1])
    assert np.abs(got[0][0]).max() > 1e-3


# --------------------------------------- (d) batch, pages and slot placement
@pytest.mark.parametrize("batch,row,slot_row", [(4, 2, 3), (2, 0, 4)])
def test_batch_composition_and_slot_placement_do_not_change_a_row(
        f32, batch, row, slot_row):
    """Solo in a one-row program against a row of a padded batch in other
    pages and another slot: the step finds a row's state by its state row,
    wherever the row stands (here the CPU's form, read / ``delta_rule_step``
    / write; the chip's kernel is held to the same above)."""
    _cfg, net, _w = f32
    tokens = np.random.default_rng(9).integers(0, 97, 20)
    solo, _x, _p = decode_logits(net, tokens, 5, pages=[1, 2, 3], slot_row=1)
    moved, extras, _p = decode_logits(net, tokens, 5, pages=[9, 4, 17],
                                      slot_row=slot_row, batch=batch, row=row)
    assert np.abs(solo - moved).max() <= TOL["float32"] * np.abs(solo).max()
    # padding is routed nowhere and counted out: five expert layers, six
    # held experts and the total; one real row, 4 assignments a layer
    moe_rows, live = (np.asarray(e) for e in extras)
    assert moe_rows.shape == (5, 7) and live.tolist() == [1]
    assert (moe_rows[:, -1] == 4).all()
    assert (moe_rows[:, :-1].sum(1) <= 4).all()


def test_a_step_leaves_the_other_slots_as_they_were(f32):
    _cfg, net, _w = f32
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


def test_a_freed_slots_state_does_not_reach_its_next_owner(f32):
    """Nothing zeroes a slot between owners; the next owner's commit
    overwrites its state whole.  A slot left full of another sequence's
    state (here: of junk) serves the same logits as a clean one."""
    _cfg, net, _w = f32
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


# ------------------------------------------------------ (e) the expert layer
def test_shares_of_a_deployment_add_up_to_the_uncut_layer():
    """Sixteen chips hold one expert each of 16: the routed parts of all 16
    shares, with the shared expert counted once, are the uncut reference
    sublayer; and a share's rows and assignments are counted right."""
    full = tiny_cfg("float32", held=tuple(range(16)), n_layer=2)
    w = ref.weights(full, seed_key(11, stream=1))
    h = jax.random.normal(jax.random.PRNGKey(2), (40, 64), jnp.float32)
    lw = {k[len("layers.1."):]: v for k, v in w.items()
          if k.startswith("layers.1.")}
    want = ref._experts(lw, h, cfg_key=ref._freeze(full, 32),
                        precision="float32") - h
    m = linear_moe._rms(h, lw["post_attention_layernorm"], 1e-5)
    shared = linear_moe._swiglu(
        m, *(lw["mlp.shared_experts." + x + "_proj"]
             for x in ("gate", "up", "down")))
    total, rows_all, assigned = shared, [], None
    for rank in range(16):
        y, rows, n_assign = routed_expert_share(
            m, lw["mlp.gate"],
            *(lw["mlp.experts." + x + "_proj"][rank:rank + 1]
              for x in ("gate", "up", "down")), (rank,), top_k=4, scale=1.0)
        total = total + y
        rows_all.append(np.asarray(rows))
        assigned = int(n_assign)
    assert float(jnp.abs(total - want).max()) <= 2e-5 * float(
        jnp.abs(want).max())
    assert assigned == 40 * 4 == int(np.concatenate(rows_all).sum())
    # one share alone is NOT the layer (the test would pass on zeros else)
    assert float(jnp.abs(shared + y - want).max()) > 0.05 * float(
        jnp.abs(want).max())


@pytest.mark.parametrize("real", [20, 7, 1])
def test_the_few_rows_share_is_the_grouped_share(real):
    """A step's expert sublayer goes by expert (a plain product chain under
    a conditional for each held expert, skipped where no row chose it), a
    prompt's by the grouped products: the same routing, result, rows and
    assignments; padding reaches no expert in either."""
    from mxnet_tpu.parallel.moe import routed_expert_share_by_expert
    k = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(k[0], (20, 32))
    router = jax.random.normal(k[1], (32, 16))
    wg, wu = (jax.random.normal(kk, (5, 32, 48)) * 0.2 for kk in k[2:4])
    wd = jax.random.normal(k[4], (5, 48, 32)) * 0.2
    held, kw = (0, 3, 4, 9, 15), dict(
        top_k=3, scale=1.5, valid=jnp.arange(20) < real)
    want = routed_expert_share(x, router, wg, wu, wd, held, **kw)
    got = jax.jit(lambda *v: routed_expert_share_by_expert(
        *v, held, **kw))(x, router, wg, wu, wd)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(got[1], want[1])
    assert int(got[2]) == int(want[2]) == 3 * real
    assert float(jnp.abs(got[0][real:]).max() if real < 20 else 0.0) == 0.0


def test_the_block_chooses_the_share_by_its_rows():
    """The step program (2 rows here, 32 in the cell) has a conditional a
    held expert a layer and no grouped product; the prefill (16 rows a
    prompt here, hundreds in the cell: above ``few_rows``) has three
    grouped products a layer and no conditional."""
    net, _w = build(tiny_cfg("float32"), fresh=True)
    cache = new_cache(net)
    p = net._params_dict(net.param_leaves())

    def count(jaxpr, name):
        n = 0
        for eqn in jaxpr.eqns:
            n += eqn.primitive.name.startswith(name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += count(sub, name)
        return n

    step = jax.make_jaxpr(lambda *a: net.step_program(*a, cache.pages))(
        p, jnp.zeros((2,), "int32"), jnp.zeros((2,), "int32"),
        jnp.zeros((2, MAX_PAGES + 1), "int32"), cache.pools).jaxpr
    # (the choice by platform of the recurrence and of the paged attention
    # is a conditional too in the traced program)
    assert count(step, "ragged_dot") == 0 and count(step, "cond") >= 5 * 6
    net.few_rows = 8                    # this test's own block
    prefill = jax.make_jaxpr(net.prefill_math)(
        p, jnp.zeros((1, 16), "int32"), jnp.ones((1,), "int32")).jaxpr
    assert count(prefill, "ragged_dot") == 5 * 3
    assert count(prefill, "cond") < 5 * 6


# ------------------------------------ (f) the cache and runtime read the block
def test_cache_builds_paged_and_slot_pools_from_the_layout():
    net, _w = build(tiny_cfg(), fresh=True)
    layout = net.cache_layout()
    assert layout["layers"] == 2 and layout["state"]["layers"] == 3
    assert net.gqa_layers == (0, 4) and net.kda_layers == (1, 2, 3)
    cache = PagedKVCache(layout=layout, page_size=PAGE, num_pages=5,
                         max_pages_per_seq=2, max_slots=3)
    k, v, kda_pool, conv_pool = cache.pools
    assert k.shape == v.shape == (2, 5, PAGE, 32) and k.dtype == jnp.bfloat16
    assert kda_pool.shape == (3, 4, 4, 32, 32) and \
        kda_pool.dtype == jnp.float32
    # the three tails, 3 inputs of 3 x 4 x 32 values, as whole lane tiles
    assert conv_pool.shape == (3, 4, 9, 128) and \
        conv_pool.dtype == jnp.bfloat16
    assert cache.kv_bytes_per_token == 2 * 2 * 32 * 2
    assert cache.table_width == 3 and cache.prefix_sharing is False
    s = cache.stats()
    assert s["state_slots_live"] == 0
    assert s["state_bytes"] == 4 * 3 * (4 * 32 * 32 * 4 + 1152 * 2) == \
        cache.state_bytes
    slot = cache.alloc(1, prompt=np.arange(9))
    assert cache.stats()["state_slots_live"] == 1
    assert cache.stats()["prefix_skipped"] == 1
    cache.free(slot)
    shapes = net.prefill_state(1, 16)
    assert [s for s, _d in shapes] == [(2, 1, 16, 32), (2, 1, 16, 32),
                                       (3, 1, 4, 32, 32), (3, 1, 9, 128)]
    rt = DecodeRuntime(net, page_size=PAGE, batch_buckets=(1, 4),
                       seq_buckets=(8, 16), warm=False)
    assert rt.cache.max_slots == 4 and rt.cache.table_width == 9
    assert rt.prefill_batch_buckets == (1,) == (net.max_prefill_batch,)


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


def test_mesh_and_bad_layers_say_so():
    net, _w = build(tiny_cfg(), fresh=True)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("model",))
    with pytest.raises(ValueError, match="slot pools, which are not sharded"):
        DecodeSession(net, page_size=PAGE, batch_buckets=(1,),
                      seq_buckets=(8,), warm=False, start=False, mesh=mesh)
    for bad in ((), (0, 1, 2), (7,)):
        with pytest.raises(ValueError, match="a grouped-query layer and a"):
            LinearMoELM(num_layers=3, gqa_layers=bad)
    with pytest.raises(ValueError, match="not divisible by"):
        LinearMoELM(num_attention_heads=5)


def test_the_step_lowered_for_the_cpu_is_the_definition(f32):
    """``kda.step.path``: lowering a step program for the CPU counts
    ``kind="plain"`` once a KDA layer and the kernel never (for the chip it
    is the other way round: ``tests/test_chip_compile.py``)."""
    _cfg, net, _w = f32
    cache = new_cache(net)
    p = net._params_dict(net.param_leaves())
    fn = jax.jit(lambda *a: net.step_program(*a, cache.pages))
    args = (p, jnp.zeros((2,), "int32"), jnp.zeros((2,), "int32"),
            jnp.zeros((2, MAX_PAGES + 1), "int32"), cache.pools)
    assert counted("kda.step.path", lambda: fn.lower(*args)) == \
        {'{kind="plain",rows="2"}': 3}


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
    sess, _net, w = session
    cfg = tiny_cfg("float32")
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, 97, n).tolist() for n in (5, 11, 3, 9, 14)]
    futs = [sess.submit(p, max_new_tokens=7) for p in prompts]
    for prompt, fut in zip(prompts, futs):
        seq = list(prompt)
        for _ in range(7):
            logits = reference_logits(w, cfg, seq)
            seq.append(int(np.argmax(logits[-1])))
        assert fut.result(timeout=120).token_ids == seq[len(prompt):]
    s = sess.stats()
    assert s["pages_in_use"] == 0 and s["slots_in_use"] == 0
    assert s["state_slots_live"] == 0 and s["state_bytes"] > 0
    assert s["prefix_skipped"] >= 5 and s["prefix_hits"] == 0


def test_step_counters_ride_the_fetch(session):
    sess, _net, _w = session
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
    # one row, five expert sublayers, 4 choices each over all 16 experts
    assert c["decode.moe.assignments"] == steps * 5 * 4
    assert c["decode.moe.layer_steps"] == steps * 5
    assert 0 < c["decode.moe.assignments_held"] <= c["decode.moe.assignments"]
    # three KDA layers a step, one live row's state each
    assert c["decode.kda.layer_steps"] == steps * 3
    assert c["decode.kda.state_rows"] == steps * 3
    # prefix sharing was asked for (the default) and skipped, not looked up
    assert c["decode.prefix.skipped"] == 1
    assert "decode.prefix_hits" not in c and "decode.prefix_misses" not in c
    assert snap["gauges"]["decode.state_slots_live"] == 0
    assert snap["gauges"]["decode.state_bytes"] == sess.cache.state_bytes
