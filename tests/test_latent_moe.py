"""The latent-attention, routed-expert decode block against its plain
reference (``perf/reference/axk1.py``), at a small size on the CPU with
seeded weights: prefill then decoding through the paged latent cache against
the reference's full forward pass, absorbed against expanded attention, the
shares of a small deployment adding up to the uncut layer, batch and page
placement, the YaRN numbers of the published configuration, and what the
cache and the runtime take from the block.

Tolerances, as a share of the largest logit.  ``float32`` runs every product
at the highest precision, so the program and the reference differ by
summation order only: 2e-5 (measured 1.0e-6 to 2.4e-6 over seeds 3 to 7).
This is the run that ties the mathematics down.  ``bfloat16`` rounds both
inputs of every product to 8 bits of mantissa (2**-9 relative) and the
cached latent rows once more; over some ten products in sequence a layer
and three layers that is about sqrt(30) * 2**-9 = 1.1% at a real width and
more at 64 wide: 4% (measured 2.0% and 2.5% at seeds 3 and 4, which the
tests use).  That holds where the program's expert choices are the
reference's.  Where the 8th and 9th router scores lie within bfloat16's
noise of the normed state the choice flips, one expert of a token's eight is
another, and that token's logits move by 15 to 35% of their scale (seeds 5,
6 and 7 at this width): no tolerance on logits covers it and none is
claimed; the router's scores are float32 so that it is rare at the real
width, and the benchmark counts the served tokens it moves.  A lower
precision than bfloat16 fails the comparison that decides ``correct``
(``tests/perf/test_axk1_cell.py``).
"""
import functools
import gc
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
from mxnet_tpu.parallel.moe import (group_limited_topk,  # noqa: E402
                                    routed_expert_share)
from mxnet_tpu.serving.decode import (CausalLM, DecodeRuntime,  # noqa: E402
                                      DecodeSession, LatentMoELM,
                                      PagedKVCache, get_decode_model)
from mxnet_tpu.serving.decode import latent_moe  # noqa: E402
from decode_block_harness import PAGE, Kit, decode_logits  # noqa: E402
from perf.harness.weights import seed_key  # noqa: E402
from perf.reference import axk1 as ref  # noqa: E402
from perf.reference import xing4 as hc_ref  # noqa: E402
from perf.systems import (hyper_latent_moe_gateway,  # noqa: E402
                          latent_moe_gateway)

TOL = {"float32": 2e-5, "bfloat16": 4e-2}
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 16,
        "type": "yarn"}

# built and compiled once a configuration: tests/decode_block_harness.py
KIT = Kit(ref, latent_moe_gateway, TOL, seed=3)
HC = Kit(hc_ref, hyper_latent_moe_gateway, TOL, seed=3)
build = KIT.build


def tiny_cfg(dtype="bfloat16", held=(0, 1, 2, 3, 8, 9), n_layer=3):
    """A.X-K1's keys at a size the CPU runs in a second; 0.2 for the
    initialiser so that the logits are of order 1."""
    return {"hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
            "kv_lora_rank": 32, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16,
            "intermediate_size": 128, "moe_intermediate_size": 32,
            "n_shared_experts": 1, "n_routed_experts": len(held),
            "held_experts": list(held), "num_experts_per_tok": 4,
            "n_group": 4, "topk_group": 2, "routed_scaling_factor": 2.5,
            "first_k_dense_replace": 1, "rms_norm_eps": 1e-6,
            "rope_theta": 10000, "rope_scaling": YARN, "vocab_size": 97,
            "n_layer": n_layer, "initializer_range": 0.2,
            "published": {"n_routed_experts": 16},
            "precision": {"weights": dtype}}


# ------------------------------------------------- (a) against the reference
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n_prompt", [1, 9])
def test_prefill_then_paged_decode_matches_reference(dtype, n_prompt):
    cfg = tiny_cfg(dtype)
    net, w = build(cfg)
    tokens = np.random.default_rng(7).integers(0, 97, 22)
    got, _x, _p = decode_logits(net, tokens, n_prompt, pages=[3, 5, 7])
    want = np.asarray(ref.forward(w, cfg, jnp.asarray(tokens)))[n_prompt - 1:]
    scale = np.abs(want).max()
    assert scale > 0.5          # logits of order 1, not a comparison of zeros
    assert np.abs(got - want).max() <= TOL[dtype] * scale


def test_float32_state_is_much_tighter_than_bfloat16():
    tokens = np.random.default_rng(8).integers(0, 97, 20)
    err = {}
    for dtype in ("float32", "bfloat16"):
        cfg = tiny_cfg(dtype)
        net, w = build(cfg, seed=4)
        got, _x, _p = decode_logits(net, tokens, 6, pages=[1, 2, 3])
        want = np.asarray(ref.forward(w, cfg, jnp.asarray(tokens)))[5:]
        err[dtype] = np.abs(got - want).max() / np.abs(want).max()
    assert err["float32"] < 2e-5 < 1e-3 < err["bfloat16"] < 4e-2


# ------------------------------------------------ (b) absorbed vs expanded
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_absorbed_attention_agrees_with_expanded(dtype):
    net, _w = build(tiny_cfg(dtype))
    p = net._params_dict(net.param_leaves())
    S = 12
    a = jax.random.normal(jax.random.PRNGKey(1), (2, S, 64), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (2, S))
    causal = jnp.tril(jnp.ones((S, S), bool))
    for layer in (0, 2):
        # compiled, one program a form and layer: op by op the two forms
        # are some hundred small programs, each compiled on its first use
        expanded = jax.jit(functools.partial(net.attend_expanded, p, layer))
        absorbed = jax.jit(functools.partial(net.attend_absorbed, p, layer))
        full, rows = expanded(a, pos, causal)
        for t in (0, 5, S - 1):
            mask = jnp.arange(S)[None, :] <= jnp.full((2, 1), t)
            one = absorbed(a[:, t], pos[:, t], rows, mask)
            scale = float(jnp.abs(full[:, t]).max())
            assert float(jnp.abs(one - full[:, t]).max()) <= \
                TOL[dtype] * scale


# ------------------------------------------------------ (c) the shares add up
def test_shares_of_a_deployment_add_up_to_the_uncut_layer():
    """Four chips hold four experts each of 16: the routed parts of all the
    shares, with the shared expert counted once, are the uncut reference
    layer; and a share's rows and assignments are counted right."""
    full = tiny_cfg("float32", held=tuple(range(16)), n_layer=2)
    w = ref.weights(full, seed_key(11, stream=1))
    h = jax.random.normal(jax.random.PRNGKey(2), (40, 64), jnp.float32)
    lw = {k[len("layers.1."):]: v for k, v in w.items()
          if k.startswith("layers.1.")}
    want = ref._moe_ffn(lw, h, cfg_key=ref._freeze(full),
                        precision="float32") - h
    m = latent_moe._rms(h, lw["post_attention_layernorm"], 1e-6)
    shared = latent_moe._swiglu(m, lw["mlp.shared_experts.gate_proj"],
                                lw["mlp.shared_experts.up_proj"],
                                lw["mlp.shared_experts.down_proj"])
    total, rows_all, assigned = shared, [], None
    for rank in range(4):
        held = tuple(range(4 * rank, 4 * rank + 4))
        ids = np.asarray(held)
        y, rows, n_assign = routed_expert_share(
            m, lw["mlp.gate"], lw["mlp.experts.gate_proj"][ids],
            lw["mlp.experts.up_proj"][ids], lw["mlp.experts.down_proj"][ids],
            held, top_k=4, n_group=4, topk_group=2, scale=2.5)
        total = total + y
        rows_all.append(np.asarray(rows))
        assigned = int(n_assign)
    assert float(jnp.abs(total - want).max()) <= 2e-5 * float(
        jnp.abs(want).max())
    # every assignment lands on exactly one share: nothing dropped, nothing
    # computed twice
    assert assigned == 40 * 4 == int(np.concatenate(rows_all).sum())
    # one share alone is NOT the layer (the test would pass on zeros else)
    assert float(jnp.abs(shared + y - want).max()) > 0.05 * float(
        jnp.abs(want).max())


def test_router_choice_follows_the_reference():
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(5),
                                              (64, 192)))
    cfg = {"n_group": 8, "topk_group": 4, "num_experts_per_tok": 8,
           "routed_scaling_factor": 2.5}
    ids, chosen = group_limited_topk(scores, 8, 8, 4)
    want_ids, want_w = ref.route(scores, cfg)
    assert (np.sort(np.asarray(ids)) == np.sort(np.asarray(want_ids))).all()
    w = 2.5 * chosen / chosen.sum(-1, keepdims=True)
    assert np.allclose(np.sort(np.asarray(w)), np.sort(np.asarray(want_w)),
                       rtol=1e-6)
    # the choice is group-limited: at most 4 of the 8 groups are touched
    assert max(len(set(r // 24)) for r in np.asarray(ids)) <= 4


def test_padding_rows_are_routed_nowhere():
    net, _w = build(tiny_cfg("float32"))
    tokens = np.random.default_rng(3).integers(0, 97, 12)
    _logits, extras, _p = decode_logits(net, tokens, 4, pages=[2, 4],
                                        batch=4, row=1)
    moe_rows = np.asarray(extras[0])
    # two expert layers, six held experts and the total; one real row of
    # four: 4 assignments a layer over all 16 experts, not 16
    assert moe_rows.shape == (2, 7)
    assert (moe_rows[:, -1] == 4).all()
    assert (moe_rows[:, :-1].sum(1) <= 4).all()


# ------------------------------------------- (d) batch and page placement
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_batched_and_replaced_row_equals_its_solo_run(dtype):
    net, _w = build(tiny_cfg(dtype))
    tokens = np.random.default_rng(9).integers(0, 97, 18)
    solo, _x, _p = decode_logits(net, tokens, 5, pages=[1, 2, 3])
    moved, _x, _p = decode_logits(net, tokens, 5, pages=[9, 4, 17], batch=4,
                                  row=2)
    assert np.abs(solo - moved).max() <= TOL[dtype] * np.abs(solo).max()


# --------------------------------------------------------------- (e) YaRN
def test_yarn_numbers_of_the_published_configuration():
    """By hand for dim 64, base 10000, factor 32, original 4096, beta 32 /
    1: the correction dims are floor(64 ln(4096 / 64 pi) / (2 ln 10000)) =
    floor(10.47) = 10 and ceil(64 ln(4096 / 2 pi) / (2 ln 10000)) =
    ceil(22.51) = 23, so pairs up to 10 keep their frequency, pairs from 23
    are slowed 32 times and pair 16 is 6/13 of the way."""
    scaling = {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
               "mscale_all_dim": 1,
               "original_max_position_embeddings": 4096, "type": "yarn"}
    cfg = {"qk_rope_head_dim": 64, "rope_theta": 10000,
           "rope_scaling": scaling, "qk_nope_head_dim": 128}
    for f in (latent_moe.yarn_inv_freq(64, 10000.0, scaling),
              np.asarray(ref.yarn_inv_freq(cfg))):
        assert f.shape == (32,)
        assert f[0] == pytest.approx(1.0)
        assert f[10] == pytest.approx(10 ** -1.25, rel=1e-9)     # kept
        assert f[23] == pytest.approx(10 ** -2.875 / 32, rel=1e-9)
        assert f[31] == pytest.approx(10 ** -3.875 / 32, rel=1e-9)
        assert f[16] == pytest.approx(0.01 * (7 / 13) + 0.01 / 32 * (6 / 13),
                                      rel=1e-9)
        assert f[16] == pytest.approx(0.005528846, rel=1e-6)
    # 192 ** -0.5 * (0.1 ln 32 + 1) ** 2
    want = 0.07216878 * 1.34657359 ** 2
    assert latent_moe.yarn_softmax_scale(192, scaling) == pytest.approx(
        want, rel=1e-6)
    assert ref.softmax_scale(cfg) == pytest.approx(0.1308610, rel=1e-5)
    # no scaling: the plain rotary
    assert latent_moe.yarn_inv_freq(8, 10000.0)[1] == pytest.approx(0.1)
    assert latent_moe.yarn_softmax_scale(192) == pytest.approx(192 ** -0.5)


# ------------------------------------- (f) the cache and runtime read the block
def test_cache_takes_its_pools_from_the_block():
    net, _w = build(tiny_cfg())
    assert net.row_width == 40 and net.pool_width == 128
    cache = PagedKVCache(layout=net.cache_layout(), page_size=PAGE,
                         num_pages=5, max_pages_per_seq=2)
    (pool,) = cache.pools
    assert pool.shape == (3, 5, PAGE, 128) and pool.dtype == jnp.bfloat16
    assert cache.kv_bytes_per_token == 3 * 128 * 2
    assert cache.num_heads is None
    # CausalLM's two pools, as they were
    lm = get_decode_model("decode_tiny", vocab_size=50, max_length=32)
    two = PagedKVCache(layout=lm.cache_layout(), page_size=PAGE, num_pages=5,
                       max_pages_per_seq=2)
    old = PagedKVCache(lm.num_layers, lm.num_heads, lm.head_dim,
                       page_size=PAGE, num_pages=5, max_pages_per_seq=2)
    assert [(p.shape, p.dtype) for p in two.pools] == \
        [(p.shape, p.dtype) for p in old.pools] == \
        [((2, 5, PAGE, 64), jnp.float32)] * 2
    assert two.k_pages is two.pools[0] and two.v_pages is two.pools[1]
    assert (two.num_heads, two.head_dim) == (old.num_heads, old.head_dim) \
        == (2, 32)
    assert two.kv_bytes_per_token == old.kv_bytes_per_token == 2 * 2 * 64 * 4


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


def test_sharded_latent_pool_says_so():
    net, _w = build(tiny_cfg())
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("model",))
    with pytest.raises(ValueError, match="shared by all heads"):
        PagedKVCache(layout=net.cache_layout(), mesh=mesh)


def test_runtime_takes_context_and_prefill_ladder_from_the_block():
    net, _w = build(tiny_cfg(), max_length=48, fresh=True)
    rt = DecodeRuntime(net, page_size=PAGE, batch_buckets=(1, 2, 4),
                       seq_buckets=(8, 16), warm=False)
    assert rt.cache.context_length == 48 and rt.cache.max_pages_per_seq == 6
    # the block prefills one prompt a call, and says so itself
    assert rt.max_batch == 4 and rt.prefill_batch_buckets == (1,)
    assert rt.max_prefill_batch == net.max_prefill_batch == 1
    with pytest.raises(ValueError, match="at most 1 prompts a call"):
        DecodeRuntime(net, page_size=PAGE, batch_buckets=(2, 4), warm=False)
    # CausalLM: the position table's length, the one ladder for both
    lm = get_decode_model("decode_tiny", vocab_size=50, max_length=32)
    lm.initialize()
    rt2 = DecodeRuntime(lm, page_size=PAGE, batch_buckets=(1, 2),
                        seq_buckets=(8,), warm=False)
    assert rt2.cache.context_length == 32
    assert rt2.prefill_batch_buckets == rt2.batch_buckets == (1, 2)
    with pytest.raises(ValueError, match="not the block's"):
        DecodeRuntime(net, cache=rt2.cache, batch_buckets=(1,), warm=False)


# ------------------------------------------------- through the normal path
@pytest.fixture(scope="module")
def session():
    net, w = build(tiny_cfg("float32"), seed=5, fresh=True)
    sess = DecodeSession(net, page_size=PAGE, batch_buckets=(1, 2, 4),
                         seq_buckets=(8, 16))
    yield sess, net, w
    sess.close(drain=False)


def test_session_serves_the_reference_greedy_stream(session):
    """Gateway's session, scheduler, runtime and cache: three requests in
    flight together each produce the reference's own greedy continuation
    (float32 state, so the argmax is the reference's)."""
    sess, net, w = session
    cfg = tiny_cfg("float32")
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, 97, n).tolist() for n in (5, 11, 3)]
    futs = [sess.submit(p, max_new_tokens=6) for p in prompts]
    for prompt, fut in zip(prompts, futs):
        seq = list(prompt)
        for _ in range(6):
            # padded to one length (every layer is causal: what follows a
            # position does not reach it), so the reference compiles once
            padded = np.zeros((24,), "int32")
            padded[:len(seq)] = seq
            logits = ref.forward(w, cfg, jnp.asarray(padded))
            seq.append(int(jnp.argmax(logits[len(seq) - 1])))
        assert fut.result(timeout=120).token_ids == seq[len(prompt):]
    s = sess.stats()
    assert s["pages_in_use"] == 0 and s["slots_in_use"] == 0


def test_step_counters_ride_the_fetch(session):
    sess, net, _w = session
    mx.telemetry.enable()
    try:
        mx.telemetry.reset()
        sess.generate(list(range(1, 8)), max_new_tokens=5, timeout=120)
        c = mx.telemetry.snapshot()["counters"]
    finally:
        mx.telemetry.disable()
    steps = c["decode.steps"]
    assert steps == 4                       # the first token is the prefill's
    # one row, two expert layers, 4 choices each over all 16 experts
    assert c["decode.moe.assignments"] == steps * 2 * 4
    assert c["decode.moe.layer_steps"] == steps * 2
    assert 0 < c["decode.moe.assignments_held"] <= c["decode.moe.assignments"]
    assert c["decode.moe.experts_hit"] == c["decode.moe.assignments_held"]
    assert c["decode.moe.max_expert_rows"] <= steps


@pytest.mark.parametrize("which", ["latent_moe", "causal_lm"])
def test_served_weights_are_held_once(which):
    """A server's parameters carry ``grad_req='null'``; the prefill's
    CachedOp returns only the state a forward changed, so no parameter is
    copied out of a prefill and rebound: after serving, every parameter is
    the very array it was loaded as, and the runtime's leaves are those."""
    if which == "latent_moe":
        net, _w = build(tiny_cfg("float32"), fresh=True)
    else:
        net = CausalLM(vocab_size=50, units=32, num_layers=2, num_heads=2,
                       max_length=32)
        net.initialize()
        net.collect_params().setattr("grad_req", "null")
    before = {n: p.data()._data for n, p in net.collect_params().items()}
    sizes = {(a.shape, str(a.dtype)) for a in before.values() if a.ndim >= 2}

    def live():
        """Live arrays of a parameter matrix's shape (other tests' blocks
        of the same size are among them: the count, not the list; what
        earlier tests left for the collector is collected first, or it may
        go between the two counts)."""
        gc.collect()
        return sum((a.shape, str(a.dtype)) in sizes
                   for a in jax.live_arrays())

    n_before = live()
    with DecodeSession(net, page_size=PAGE, batch_buckets=(1, 2),
                       seq_buckets=(8, 16)) as sess:
        sess.generate([1, 2, 3, 4, 5], max_new_tokens=4, timeout=120)
        after = {n: p.data()._data for n, p in net.collect_params().items()}
        assert all(after[n] is before[n] for n in before)
        assert all(a is b for a, b in zip(sess.runtime._params,
                                          net.param_leaves()))
        assert live() == n_before
        # and the compiled prefill has the two outputs of the forward
        op = net._cached_op
        assert all(h[0] == [] for h in op._aux_changed.values())


# ----------------------------- (g) the hyper-connected residual path (mHC)
def hc_cfg(dtype="float32", first_dense=1, bias_std=0.05,
           held=(0, 1, 2, 3, 8, 9), n_layer=2):
    """Xing4.0's keys at the tiny size: one group, the selection bias, four
    streams; ``phi`` drawn wide enough (0.15 x sqrt(256) = 2.4, the
    published widths' 0.02 x sqrt(14336)) that the coefficients differ from
    token to token."""
    cfg = tiny_cfg(dtype, held, n_layer)
    cfg.update({
        "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
        "first_k_dense_replace": first_dense, "routed_scaling_factor": 2.0,
        "selection_bias_std": bias_std, "hc_mult": 4,
        "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30,
        "hc_init": {"phi_std": 0.15, "alpha": [0.3, 0.3, 0.3],
                    "res_diagonal": 1.5}})
    return cfg


HC_TOKENS = np.random.default_rng(0).integers(0, 97, 12)


def hc_reference(w, cfg, precision="float32"):
    return np.asarray(hc_ref.forward(w, cfg, jnp.asarray(HC_TOKENS),
                                     precision))[8:]


@pytest.mark.parametrize("dtype,variant", [
    ("float32", {}), ("bfloat16", {}),
    ("float32", {"first_dense": 2, "n_layer": 3}),
    ("float32", {"bias_std": 1.0})], ids=["float32", "bfloat16", "dense2",
                                          "bias"])
def test_hyper_connected_prefill_then_decode_matches_reference(dtype,
                                                               variant):
    """Prefill of 9 tokens, then 3 steps through the paged cache, on four
    streams: float32 to 1e-4 of the largest logit (measured 1.2e-6: the
    mathematics), bfloat16 to 4e-2 (measured 1.6e-2), which an e4m3 rounding
    of the weights fails (it moves the REFERENCE by 0.16).  Two leading dense
    layers and a wide selection bias move the answer, and the program
    follows the reference there too."""
    cfg = hc_cfg(dtype, **variant)
    net, w = HC.build(cfg)
    got, extras, _p = decode_logits(net, HC_TOKENS, 9, pages=[3, 5])
    want = hc_reference(w, cfg)
    scale = np.abs(want).max()
    assert scale > 0.5
    tol = {"float32": 1e-4, "bfloat16": TOL["bfloat16"]}[dtype]
    assert np.abs(got - want).max() <= tol * scale
    # the step's extras: the experts' rows, then the bits of the worst
    # distance of a live row's Hres from doubly stochastic
    moe_rows, resid = (np.asarray(e) for e in extras)
    assert moe_rows.shape == (1, 7)
    assert 0 <= float(resid.view(np.float32)) < 1e-4
    if dtype == "bfloat16":
        fp8 = hc_reference(w, cfg, "weights_fp8")
        assert np.abs(fp8 - want).max() > 2 * tol * scale
    if "bias_std" in variant:
        # the wide bias is not a rounding: on the same weights with the bias
        # zeroed the reference reads another answer
        unbiased = {k: jnp.zeros_like(v)
                    if k.endswith("e_score_correction_bias") else v
                    for k, v in w.items()}
        assert np.abs(hc_reference(unbiased, cfg) - want).max() \
            > 0.01 * scale


@pytest.mark.parametrize("control", ["sinkhorn_off", "hc_static"])
def test_a_broken_mixing_is_another_model(control):
    """The two controls of the benchmark's check move the reference's
    logits by tens of percent: no Sinkhorn round, or coefficients that no
    longer depend on the token, are not within any tolerance above."""
    cfg = hc_cfg(n_layer=3)
    w = hc_ref.weights(cfg, seed_key(3, stream=1))
    want = hc_reference(w, cfg)
    assert np.abs(hc_reference(w, cfg, control) - want).max() \
        > 0.1 * np.abs(want).max()


def test_plain_residual_path_registers_and_computes_nothing_new():
    """``hc_mult == 1``: no hyper-connection or selection-bias parameter,
    the streams are the hidden state itself, and a sublayer is ``h +
    f(norm(h))`` bit for bit (the benchmark's ``axk1_ep16`` programs lower
    to the same text as before: ``PERF.md`` section 4)."""
    net, _w = build(tiny_cfg("float32"))
    assert not [n for n in net._param_order if "hc_" in n or "bias" in n]
    p = net._params_dict(net.param_leaves())
    h = jax.random.normal(jax.random.PRNGKey(4), (5, 64), jnp.float32)
    assert net._streams(h) is h and net._merged(h) is h
    part = jnp.tanh(h)
    seen = []
    got = net._sublayer(p, 1, "ffn", h,
                        lambda m: (seen.append(m) or part, 2 * part))
    want = h + part + 2 * part
    assert (np.asarray(got) == np.asarray(want)).all()
    assert (np.asarray(seen[0]) == np.asarray(
        latent_moe._rms(h, p["l1_norm_ffn"], 1e-6))).all()
    with pytest.raises(ValueError, match="hc_mult"):
        LatentMoELM(hc_mult=0)


def test_shares_of_the_hyper_connected_expert_sublayer_add_up():
    """Eight chips hold two experts each of 16: written back once, the
    routed parts of all the shares with the shared expert counted once are
    the uncut reference's expert sublayer on the four streams."""
    full = hc_cfg(held=tuple(range(16)), bias_std=0.3)
    w = hc_ref.weights(full, seed_key(11, stream=1))
    lw = {k[len("layers.1."):]: v for k, v in w.items()
          if k.startswith("layers.1.")}
    X = jax.random.normal(jax.random.PRNGKey(2), (40, 4, 64), jnp.float32)
    want = hc_ref._moe_sublayer(lw, X, cfg_key=hc_ref._freeze(full),
                                precision="float32")
    from mxnet_tpu.ops import hyper_connection as hc
    h_pre, h_post, h_res = hc.hc_coefficients(
        X, {"phi": lw["hc_ffn.phi"], "a": lw["hc_ffn.alpha"],
            "b": lw["hc_ffn.bias"]}, 20, 1e-6, (-30.0, 30.0))
    m = latent_moe._rms(hc.hc_read(X, h_pre),
                        lw["post_attention_layernorm"], 1e-6)
    total = latent_moe._swiglu(m, lw["mlp.shared_experts.gate_proj"],
                               lw["mlp.shared_experts.up_proj"],
                               lw["mlp.shared_experts.down_proj"])
    one, n_rows = None, 0
    for rank in range(8):
        held = (2 * rank, 2 * rank + 1)
        ids = np.asarray(held)
        y, rows, n_assign = routed_expert_share(
            m, lw["mlp.gate"], lw["mlp.experts.gate_proj"][ids],
            lw["mlp.experts.up_proj"][ids], lw["mlp.experts.down_proj"][ids],
            held, top_k=4, scale=2.0,
            select_bias=lw["mlp.gate.e_score_correction_bias"])
        one = total + y if one is None else one
        total = total + y
        n_rows += int(np.asarray(rows).sum())
    scale = float(jnp.abs(want).max())
    got = hc.hc_write(X, h_res, h_post, total)
    assert float(jnp.abs(got - want).max()) <= 2e-5 * scale
    assert n_rows == int(n_assign) == 40 * 4
    # one share alone is NOT the sublayer
    assert float(jnp.abs(hc.hc_write(X, h_res, h_post, one) - want).max()) \
        > 0.01 * scale
