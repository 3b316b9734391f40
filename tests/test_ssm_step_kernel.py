"""The decode step's recurrence kernel (``ops.pallas_kernels.ssm_step_slots``)
under the Pallas interpreter, held to its definition ``ops.ssm.ssm_step`` on
the rows gathered out of the pool, and to what it may touch: the live rows'
states of ITS layer and nothing else.  What the chip's compiler makes of it
is ``tests/test_chip_compile.py``'s; how fast it is, ``PERF.md``'s."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import ssm
from mxnet_tpu.ops.pallas_kernels import by_platform, ssm_step_slots
from mxnet_tpu.test_utils import counted

LAYERS, SLOTS, H, P, N = 3, 9, 8, 16, 128

# (the batch's state rows, 0 a padded row; heads a group; layer): a full
# batch, padding behind the live rows (as the scheduler packs them), in front
# of them and between them, one row, no live row; slots in order, permuted,
# far apart; B and C a head, shared by 2 and by 8
CASES = [
    ((1,), 8, 0),
    ((7,), 1, 2),
    ((1, 2, 3, 4), 8, 0),
    ((3, 1, 4, 2), 1, 1),
    ((9, 2, 0, 0), 8, 2),
    ((5, 0, 0, 0), 1, 0),
    ((0, 0, 0, 0), 8, 1),
    ((8, 1, 6, 3, 9, 2, 7, 4), 8, 2),
    ((2, 9, 5, 0, 0, 0, 0, 0), 1, 0),
    ((9, 7, 5, 3, 1, 0, 0, 0), 8, 1),
    ((4, 8, 0, 0, 0, 0, 0, 0), 2, 2),
    ((0,), 1, 0),
    ((0, 6, 0, 0), 8, 0),
    ((0, 0, 3, 0, 9, 0, 0, 1), 2, 1),
]


def _inputs(rows, per_group, seed=0):
    rows = np.asarray(rows, np.int32)
    b, G = len(rows), H // per_group
    k = jax.random.split(jax.random.PRNGKey(seed + 31 * b + int(rows.sum())),
                         8)
    return dict(
        pool=jax.random.normal(k[0], (LAYERS, SLOTS + 1, H, P, N)),
        rows=jnp.asarray(rows),
        x=jax.random.normal(k[1], (b, H, P)),
        dt=jax.nn.softplus(jax.random.normal(k[2], (b, H))),
        A=-jnp.exp(jax.random.normal(k[3], (H,))),
        B=jax.random.normal(k[5], (b, G, N)),
        C=jax.random.normal(k[6], (b, G, N)),
        D=jax.random.normal(k[4], (H,)))


def _run(v, layer, **kw):
    return ssm_step_slots(v["pool"], layer, v["rows"], v["x"], v["dt"],
                          v["A"], v["B"], v["C"], v["D"], interpret=True,
                          **kw)


@pytest.mark.parametrize("rows,per_group,layer", CASES)
def test_live_rows_follow_the_definition(rows, per_group, layer):
    """``y`` and the new state of every live row are ``ssm_step``'s on that
    row's state, wherever its slot lies and wherever the row stands in the
    batch; a padded row's ``y`` is ``D x`` alone (the state's part of it
    zero): for the caller to ignore."""
    v = _inputs(rows, per_group)
    pool, y = _run(v, layer)
    want_state, want_y = ssm.ssm_step(
        v["pool"][layer, v["rows"]], v["x"], v["dt"], v["A"], v["B"],
        v["C"], v["D"])
    live = np.asarray(rows) != 0
    assert y.shape == (len(rows), H, P) and y.dtype == jnp.float32
    np.testing.assert_allclose(y[live], want_y[live], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(pool[layer, v["rows"][live]],
                               want_state[live], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(y[~live],
                                  (v["D"][:, None] * v["x"])[~live])


@pytest.mark.parametrize("rows,per_group,layer", CASES)
def test_nothing_else_is_touched(rows, per_group, layer):
    """Every slot no live row names, the trash row, and every other layer
    keep their bits; the pool keeps its shape and stays float32."""
    v = _inputs(rows, per_group, seed=5)
    pool, _y = _run(v, layer)
    assert pool.shape == v["pool"].shape and pool.dtype == jnp.float32
    live = [r for r in rows if r]
    same = np.ones((LAYERS, SLOTS + 1), bool)
    same[layer, live] = False
    assert same[:, 0].all()
    np.testing.assert_array_equal(np.asarray(pool)[same],
                                  np.asarray(v["pool"])[same])
    if live:
        assert (np.asarray(pool)[~same] != np.asarray(v["pool"])[~same]).any()


def test_layer_is_an_operand_not_the_kernels_text():
    """One traced function serves every layer: the layer is a scalar the
    kernel prefetches, so 23 layers of 2 step programs lower one body."""
    v = _inputs((3, 1, 0, 0), 8)

    @jax.jit
    def f(pool, layer):
        return ssm_step_slots(pool, layer, v["rows"], v["x"], v["dt"],
                              v["A"], v["B"], v["C"], v["D"], interpret=True)

    for layer in (0, 2):
        pool, y = f(v["pool"], jnp.int32(layer))
        want, want_y = _run(v, layer)
        np.testing.assert_allclose(pool, want, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    assert f._cache_size() == 1


def test_chained_layers_advance_each_their_own():
    """Three layers' calls on one pool, as a step program chains them: each
    layer's live rows moved once, by that layer's inputs."""
    vs = [_inputs((6, 2, 9, 0), 8, seed=s) for s in range(LAYERS)]
    pool = vs[0]["pool"]
    for layer, v in enumerate(vs):
        pool, _y = _run(dict(v, pool=pool), layer)
    for layer, v in enumerate(vs):
        want, _ = ssm.ssm_step(vs[0]["pool"][layer, v["rows"]], v["x"],
                               v["dt"], v["A"], v["B"], v["C"], v["D"])
        np.testing.assert_allclose(pool[layer, v["rows"][:3]], want[:3],
                                   rtol=1e-5, atol=1e-5)


def test_through_the_slot_state_it_equals_read_step_write():
    """The two forms ``mamba_step`` builds, on one cache's pools: the
    kernel handed the whole named pool by ``SlotState.in_place`` against
    ``read`` -> ``ssm_step`` -> ``write``; the page pools and the
    convolution's tails are the same arrays before and after."""
    from mxnet_tpu.serving.decode import HybridSSMMoELM, PagedKVCache
    net = HybridSSMMoELM(pattern="M*MM", mamba_num_heads=8, mamba_head_dim=16,
                         ssm_state_size=128, n_groups=2, dtype="float32")
    cache = PagedKVCache(layout=net.cache_layout(), page_size=8, num_pages=5,
                         max_pages_per_seq=2, max_slots=SLOTS)
    slots = cache.pages.state
    v = _inputs((4, 0, 7, 1), 4, seed=3)
    pools = list(cache.pools)
    pools[slots.first] = v["pool"]
    step = (v["x"], v["dt"], v["A"], v["B"], v["C"], v["D"])
    got, y = slots.in_place(
        tuple(pools), 2, v["rows"], "ssm", lambda pool, layer, rows:
        ssm_step_slots(pool, layer, rows, *step, interpret=True))
    (state,) = slots.read(tuple(pools), 2, v["rows"], ("ssm",))
    state, want_y = ssm.ssm_step(state, *step)
    want = slots.write(tuple(pools), 2, v["rows"], (state,), ("ssm",))
    live = np.asarray(v["rows"]) != 0
    np.testing.assert_allclose(y[live], want_y[live], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[slots.first][:, 1:],
                               want[slots.first][:, 1:], rtol=1e-5,
                               atol=1e-5)
    # the plain form's padded row writes the trash row; the kernel does not
    np.testing.assert_array_equal(got[slots.first][:, 0], v["pool"][:, 0])
    for j, pool in enumerate(pools):
        if j != slots.first:
            assert got[j] is pool


def test_by_platform_counts_the_branch_that_is_lowered():
    """Both branches are traced, ONE is lowered, and the counter names it
    once a lowering: on the CPU the plain one."""
    f = jax.jit(lambda x: by_platform(
        "test.step.path", (x, x), kernel=lambda xs: xs[0] + 1,
        plain=lambda xs: xs[0] + 2, rows=3))
    got = counted("test.step.path", lambda: f(jnp.float32(1)))
    assert got == {'{kind="plain",rows="3"}': 1}
    assert float(f(jnp.float32(1))) == 3.0


def test_cpu_step_program_takes_the_plain_form_once_a_mamba_layer():
    """``ssm.step.path``: a step program of the hybrid block lowered for
    the CPU counts ``kind="plain"`` once a Mamba layer, with its batch (the
    chip's side, ``kernel``: ``tests/test_chip_compile.py``)."""
    from mxnet_tpu.serving.decode import (DecodeRuntime, HybridSSMMoELM,
                                          PagedKVCache)
    net = HybridSSMMoELM(pattern="ME*MM", dtype="float32")
    net.initialize()
    cache = PagedKVCache(layout=net.cache_layout(), page_size=8,
                         num_pages=9, max_pages_per_seq=4, max_slots=4)
    rt = DecodeRuntime(net, cache=cache, batch_buckets=(1, 4),
                       seq_buckets=(16,), warm=False)
    b, i32 = 4, "int32"
    args = (rt._params, np.zeros(b, i32), np.zeros(b, i32),
            np.zeros((b, cache.table_width), i32), np.zeros((b, 2), "uint32"),
            np.zeros(b, i32), np.zeros(b, "float32")) + tuple(cache.pools)
    step = rt._build_step()
    got = counted("ssm.step.path", lambda: step.lower(*args))
    assert got == {'{kind="plain",rows="4"}': 3}
