"""The latent-attention block's prefill kernel (``ops.pallas_kernels.
mla_prefill_attention``: the causal attention of a whole padded prompt in the
expanded form, by blocks, the score tile in VMEM only) under the Pallas
interpreter, held to its definition, ``LatentMoELM.attend_expanded``'s score
chain (``_scores_chain``: every head's ``(S, S)`` float32 scores, a ``where``,
one softmax, ``p . v``; what a prefill program lowered for the CPU runs and
what tier-1 and the plain reference are held to), and to what it may compute:
the key blocks up to a query block's diagonal and nothing past it.  Published
head widths (128 unrotated, 64 rotated, 128 values) and blocks of 128 to 768
positions.  What the chip's compiler makes of it is
``tests/test_chip_compile.py``'s; how fast it is, ``PERF.md``'s.

Tolerances against the largest value expected: float32 operands multiply at
the highest precision and differ from the definition by summation order
alone; bfloat16 operands take the queries and the probabilities as bfloat16
too, and the kernel's output is rounded to bfloat16 once more (what ``W_o``'s
product does to the definition's)."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import pallas_kernels
from mxnet_tpu.ops.pallas_kernels import mla_prefill_attention
from mxnet_tpu.serving.decode import LatentMoELM

NOPE, ROPE, WIDTH = 128, 64, 128
TOL = {"float32": 1e-5, "bfloat16": 5e-2}


@functools.lru_cache(maxsize=None)
def _block(heads, dtype, hc_mult=1, lanes=True):
    """A declared block (no array) at the published head widths, or at the
    tiny defaults' (``lanes=False``): its score chain and its scale are what
    is asked about."""
    widths = dict(qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE,
                  v_head_dim=WIDTH) if lanes else {}
    return LatentMoELM(vocab_size=64, hidden_size=128, num_layers=2,
                       num_heads=heads, q_lora_rank=32, kv_lora_rank=128,
                       intermediate_size=64, moe_intermediate_size=32,
                       n_routed_experts=4, num_experts_per_tok=2, n_group=1,
                       topk_group=1, max_length=512, dtype=dtype,
                       hc_mult=hc_mult, **widths)


def _operands(heads, s, dtype, seed=0, b=1):
    """``(q_nope, q_rope, kv, kr)`` as ``LatentMoELM._attend_whole`` makes
    them: float32 queries and per-head keys and values, the shared rotated
    key in the stored dtype."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (b, s, heads, NOPE)),
            jax.random.normal(keys[1], (b, s, heads, ROPE)),
            jax.random.normal(keys[2], (b, s, heads, NOPE + WIDTH)),
            jax.random.normal(keys[3], (b, s, ROPE)).astype(dtype))


def _kernel(net, q_nope, q_rope, kv, kr, **blocks):
    return np.asarray(mla_prefill_attention(
        q_nope, q_rope, kv.astype(net.dtype), kr, scale=net._scale,
        interpret=True, **blocks).astype(jnp.float32))


def _chain(net, q_nope, q_rope, kv, kr):
    s = q_nope.shape[1]
    return np.asarray(net._scores_chain(
        q_nope, q_rope, kv, kr, jnp.tril(jnp.ones((s, s), bool))))


# name -> (heads, S, dtype, block_q, block_k, prompts)
CASES = {
    "one-block-f32": (32, 128, "float32", 128, 128, 1),
    "one-block-bf16": (64, 128, "bfloat16", 128, 128, 1),
    "three-blocks-f32": (32, 384, "float32", 128, 128, 1),
    "three-blocks-bf16-64-heads": (64, 384, "bfloat16", 128, 128, 1),
    # the blocks the program picks for the length: 256 queries, and the
    # prompt's 512 or 768 keys ONE block, of which a query block reads its
    # first 256, 512 or 768 (a case each)
    "picked-blocks-bf16": (32, 512, "bfloat16", None, None, 1),
    "picked-blocks-f32": (2, 768, "float32", None, None, 1),
    # 128 queries over blocks of 384 keys: three cases in each of two blocks
    "narrow-queries-f32": (2, 768, "float32", 128, 384, 1),
    # a query block over two key blocks, and two query blocks a key block
    "wide-queries-f32": (2, 512, "float32", 256, 128, 1),
    "wide-keys-bf16": (2, 512, "bfloat16", 128, 256, 1),
    # two prompts a call: the leading grid axis
    "two-prompts-f32": (4, 256, "float32", 128, 128, 2),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_is_the_score_chain_of_the_definition(name):
    """The kernel's ``(B, S, H v)`` is ``attend_expanded``'s score chain's
    on the same operands, within the dtype's tolerance of the largest
    output: one block, several, the blocks the program picks, blocks of
    unequal sizes, 32 and 64 heads, one prompt and two."""
    heads, s, dtype, bq, bk, b = CASES[name]
    net = _block(heads, dtype)
    ops = _operands(heads, s, dtype, seed=len(name), b=b)
    got = _kernel(net, *ops, block_q=bq, block_k=bk)
    want = _chain(net, *ops)
    assert got.shape == want.shape == (b, s, heads * WIDTH)
    assert np.abs(got - want).max() <= TOL[dtype] * np.abs(want).max()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("length", [1, 100, 129, 300])
def test_a_prompt_that_ends_inside_a_block_needs_no_mask_of_its_own(
        length, dtype):
    """A prompt of ``length`` tokens padded to 384: whatever the padded
    positions hold (here values a thousand times a real one's), the outputs
    of the valid queries are the definition's over the ``length`` tokens
    ALONE (the causal order hides every key past a valid query: there is no
    length operand), and the outputs of the padded queries, which nobody
    reads, are finite."""
    heads, s = 4, 384
    net = _block(heads, dtype)
    ops = _operands(heads, s, dtype, seed=length)
    pad = (jnp.arange(s) >= length)
    loud = [jnp.where(pad.reshape((1, s) + (1,) * (x.ndim - 2)),
                      (1e3 * x).astype(x.dtype), x) for x in ops]
    got = _kernel(net, *loud, block_q=128, block_k=128)
    assert np.isfinite(got).all()
    want = _chain(net, *(x[:, :length] for x in ops))
    assert np.abs(got[:, :length] - want).max() <= \
        TOL[dtype] * np.abs(want).max()


@pytest.mark.parametrize("bq,bk,clean", [(128, 128, 128), (128, 256, 256),
                                         (256, 128, 256)])
def test_key_blocks_past_the_diagonal_are_not_computed(bq, bk, clean):
    """The first query block's diagonal block is its only live one.  With
    NaN for every key and value past the first ``clean`` positions (a
    probability of zero times a NaN is a NaN: a block that were computed and
    masked afterwards would show), the outputs of the queries before
    ``clean`` are finite and the definition's over those positions alone;
    only queries that may read a poisoned key give NaN."""
    heads, s = 2, 512
    net = _block(heads, "float32")
    ops = _operands(heads, s, "float32", seed=bq + bk)
    past = (jnp.arange(s) >= clean)
    q_nope, q_rope, kv, kr = ops
    bad = (q_nope, q_rope,
           jnp.where(past[None, :, None, None], jnp.nan, kv),
           jnp.where(past[None, :, None], jnp.nan, kr))
    got = _kernel(net, *bad, block_q=bq, block_k=bk)
    assert np.isfinite(got[:, :clean]).all()
    assert np.isnan(got[:, clean:]).all()
    want = _chain(net, *(x[:, :clean] for x in ops))
    assert np.abs(got[:, :clean] - want).max() <= 1e-5 * np.abs(want).max()


def _prefill(net, seed=0, s=128, lengths=(77,)):
    """``(logits, rows)`` of ``prefill_math`` on seeded weights."""
    keys = jax.random.split(jax.random.PRNGKey(seed), len(net._param_order))
    p = {}
    for k, n in zip(keys, net._param_order):
        par = net._reg_params[n]
        shape = par.shape[::-1] if n.endswith("_phi") else par.shape
        p[n] = jnp.ones(shape, par.dtype) if "norm" in n else \
            (0.05 * jax.random.normal(k, shape)).astype(par.dtype)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (len(lengths), s), 0, net.vocab_size)
    logits, rows = net.prefill_math(p, tokens, jnp.asarray(lengths))
    return np.asarray(logits), np.asarray(rows.astype(jnp.float32))


@pytest.mark.parametrize("lanes,hc_mult,dtype", [
    (True, 1, "float32"), (True, 4, "float32"), (True, 1, "bfloat16"),
    (False, 1, "float32")])
def test_prefill_lowered_for_the_cpu_is_the_definition_bit_for_bit(
        lanes, hc_mult, dtype, monkeypatch):
    """``prefill_math`` reaches its attention through ``by_platform``;
    lowered for the CPU (here) its logits and the latent rows it emits are,
    bit for bit, those of the same program with ``attend_expanded``'s score
    chain, the definition, put in its place: at the published head widths (where the
    chip would get the kernel), with and without hyper-connections, and at
    the tiny widths (the definition on every platform)."""
    net = _block(2, dtype, hc_mult, lanes)
    got = _prefill(net)
    monkeypatch.setattr(LatentMoELM, "_scores_lowered",
                        LatentMoELM._scores_chain)
    want = _prefill(net)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("hc_mult,dtype,lengths", [
    (1, "float32", (77,)), (4, "float32", (128,)),
    (1, "bfloat16", (100, 3))])
def test_prefill_through_the_kernel_is_the_definition_within_tolerance(
        hc_mult, dtype, lengths, monkeypatch):
    """The same program with ``by_platform`` made to take the kernel (under
    the interpreter), as a lowering for the chip does: the first layer's
    latent rows, which no attention precedes, are the definition's bit for
    bit (the kernel does not make them), and the logits and the second
    layer's rows agree within the stored dtype's tolerance."""
    net = _block(2, dtype, hc_mult)
    want = _prefill(net, lengths=lengths)
    monkeypatch.setattr(
        pallas_kernels, "by_platform",
        lambda counter, *args, kernel, plain, **labels:
        (kernel if counter == "decode.mla.prefill.lowered" else plain)(
            *args))
    monkeypatch.setattr(
        pallas_kernels, "mla_prefill_attention",
        functools.partial(mla_prefill_attention, interpret=True))
    logits, rows = _prefill(net, lengths=lengths)
    np.testing.assert_array_equal(rows[0], want[1][0])
    tol = 1e-4 if dtype == "float32" else 5e-2
    for g, w in ((logits, want[0]), (rows[1], want[1][1])):
        assert np.isfinite(g).all()
        assert np.abs(g - w).max() <= tol * np.abs(w).max()
