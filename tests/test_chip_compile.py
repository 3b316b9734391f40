"""Ask the chip's compiler before the chip.

The TPU compiler is installed in the CPU sandbox and compiles for a chip
that is *described*, not attached (``jax.experimental.topologies``).  These
are the kept compiles of the main path at real widths: what the v5e compiler
would refuse on the machine with the chip, it refuses here, at no chip time.
Nothing runs, so nothing here says a result is right — ``chip_smoke.py``
does that on the chip.  The kernels and the decode blocks' programs are here;
the BERT training step's are in ``test_chip_compile_bert_step.py`` (a file is
one worker's, and that step alone compiles for four minutes); ``one_chip`` is
``conftest.py``'s.
"""
import functools
import re

import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import pallas_kernels


def _flash_text(shape, dtype, causal, sharding, grad=False, masked=False):
    """HLO of the forward (or of dq, dk, dv) compiled for the described
    chip; ``masked`` adds the key mask and dropout's keep-mask."""
    b, h, t, _ = shape
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=sharding)
    x = sds(shape, dtype)

    def attend(q, k, v, mask, keep):
        return pallas_kernels.flash_attention(
            q, k, v, causal, 0.125, None, None, None,
            mask if masked else None, keep if masked else None,
            0.1 if masked else 0.0)
    fn = attend if not grad else jax.grad(
        lambda *a: attend(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    return jax.jit(fn).lower(x, x, x, sds((b, t), jnp.float32),
                             sds((b * h, t, t), jnp.int8)).compile().as_text()


@pytest.mark.parametrize("shape,dtype", [
    ((32, 12, 128, 64), jnp.bfloat16),      # BERT-base b32 x s128
    ((32, 12, 128, 64), jnp.float32),
    ((1, 12, 8192, 64), jnp.bfloat16),      # long sequence, one row
    ((1, 12, 200, 64), jnp.bfloat16),       # padded T: keys masked by bias
])
def test_flash_kernel_compiles_for_v5e(one_chip, shape, dtype):
    for causal in (False, True):
        assert "tpu_custom_call" in _flash_text(shape, dtype, causal,
                                                one_chip)


@pytest.mark.parametrize("shape,dtype", [
    ((1, 12, 32768, 64), jnp.bfloat16),
    ((1, 12, 16128, 64), jnp.bfloat16),
    ((1, 12, 8064, 64), jnp.float32),
])
def test_flash_forward_has_no_sequence_limit(one_chip, shape, dtype):
    """K and V ride a block a step, not whole: lengths the whole-sequence
    kernel refused (it stopped at 16,000 tokens in bfloat16, 7,936 in
    float32) compile."""
    assert "tpu_custom_call" in _flash_text(shape, dtype, False, one_chip)


@pytest.mark.parametrize("shape,dtype,masked", [
    ((32, 12, 512, 64), jnp.float32, True),     # the training cell's layer
    ((32, 12, 128, 64), jnp.bfloat16, False),
    ((2, 3, 200, 64), jnp.float32, True),       # odd heads: one a block
    ((1, 12, 8192, 64), jnp.bfloat16, False),
])
def test_flash_backward_compiles_for_v5e(one_chip, shape, dtype,
                                         masked):
    """dq, dk and dv are kernels too: forward and backward, two Mosaic
    calls, and with the mask and dropout no float32 (.., T, T) value."""
    text = _flash_text(shape, dtype, False, one_chip, grad=True,
                       masked=masked)
    assert text.count("tpu_custom_call") >= 2
    b, h, t, _ = shape
    assert f"f32[{b * h},{t},{t}]" not in text
    assert f"f32[{b},{h},{t},{t}]" not in text


def test_flash_backward_limit_is_the_compilers(one_chip):
    """The backward holds dK and dV as whole-sequence blocks and asks the
    compiler for the VMEM they take, so 16,384 tokens, over Mosaic's default
    budget, compile; past a core's own VMEM the chip's compiler refuses the
    kernel and that is what the caller sees, never the dense formula."""
    assert "tpu_custom_call" in _flash_text(
        (1, 12, 16384, 64), jnp.bfloat16, False, one_chip, grad=True)
    with pytest.raises(Exception, match="(?i)vmem"):
        _flash_text((1, 12, 65536, 64), jnp.bfloat16, False, one_chip,
                    grad=True)


@pytest.mark.parametrize("b", [1, 32])
@pytest.mark.parametrize("g,r,dk,dv,pages,layers,num_pages", [
    (4, 16, 192, 128, 288, 2, 18433),       # MiMo-V2.5's global layers
    (2, 16, 128, 128, 96, 6, 6145)])        # Nemotron-3-Nano's attention
def test_paged_attention_kernel_compiles_for_v5e(one_chip, b, g, r, dk, dv,
                                                 pages, layers, num_pages):
    """The decode step's paged-attention kernel at the two cells' shapes,
    pools as served (bfloat16, pages of 16 tokens, the whole row minor): one
    ``tpu_custom_call`` whose K and V operands are the pools themselves, no
    copy, slice or relayout of one before it."""
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    k_pool = sds((layers, num_pages, 16, g * dk), jnp.bfloat16)
    v_pool = sds((layers, num_pages, 16, g * dv), jnp.bfloat16)
    compiled = jax.jit(pallas_kernels.paged_attention).lower(
        sds((b, g, r, dk), jnp.float32), k_pool, v_pool, sds((), jnp.int32),
        sds((b, pages), jnp.int32), sds((b,), jnp.int32)).compile()
    assert _one_kernel_reads(compiled.as_text(), 2) == ["1", "2"]
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20) \
        + b * g * r * g * dk * 2


def _one_kernel_reads(hlo_text, n):
    """The parameter numbers behind the last ``n`` operands (the pools) of
    the program's ONE ``tpu_custom_call``; ``None`` for an operand that is
    not a bfloat16 parameter itself (a copy, a slice, a relayout of one)."""
    assert hlo_text.count('custom_call_target="tpu_custom_call"') == 1
    call = re.search(r"custom-call\((.*?)\), custom_call_target", hlo_text)
    operands = re.findall(r"%([\w.\-]+)", call.group(1))
    params = dict(re.findall(r"%([\w.\-]+) = bf16\[[\d,]+\]\S* "
                             r"parameter\((\d)\)", hlo_text))
    return [params.get(o) for o in operands[-n:]]


@pytest.mark.parametrize("b", [1, 32])
def test_paged_latent_kernel_compiles_for_v5e(one_chip, b):
    """The decode step's latent paged-attention kernel at A.X-K1's shape as
    its cell serves it (64 heads over one 640-wide bfloat16 row, pages of 16
    tokens, 128 a row): one ``tpu_custom_call`` whose pool operand is the
    pool itself, no copy, slice or relayout of it before, and nothing held
    beside the queries and the context."""
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    fn = functools.partial(pallas_kernels.paged_latent_attention, scale=0.1)
    compiled = jax.jit(fn).lower(
        sds((b, 64, 640), jnp.float32),
        sds((5, _L_PAGES, 16, 640), jnp.bfloat16), sds((), jnp.int32),
        sds((b, _L_ROW_PAGES), jnp.int32), sds((b,), jnp.int32)).compile()
    assert _one_kernel_reads(compiled.as_text(), 1) == ["1"]
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20) \
        + b * 64 * 640 * 2


@pytest.mark.parametrize("kv_dtype", ["float32", "int8", "fp8_e4m3"])
def test_decode_base_step_program_compiles_for_v5e(one_chip, kv_dtype):
    """One ``decode_base`` step program per KV pool dtype (v5e has no
    native fp8: the converts must still lower)."""
    import numpy as np
    from mxnet_tpu.serving.decode import DecodeRuntime, get_decode_model

    net = get_decode_model("decode_base")
    net.initialize()
    rt = DecodeRuntime(net, batch_buckets=(4,), seq_buckets=(16,),
                       page_size=16, kv_dtype=kv_dtype, warm=False)
    b, pages = 4, rt.cache.max_pages_per_seq

    def sds(a):
        return jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=one_chip)

    args = ([sds(p) for p in rt._params],
            sds(np.zeros((b,), "int32")), sds(np.zeros((b,), "int32")),
            sds(np.zeros((b, pages), "int32")),
            sds(np.zeros((b, 2), "uint32")), sds(np.zeros((b,), "int32")),
            sds(np.zeros((b,), "float32"))) + \
        tuple(sds(p) for p in rt.cache.pools)
    compiled = rt._build_step().lower(*args).compile()
    # the donated pools come back in place: all of them alias
    stats = compiled.memory_analysis()
    pool_bytes = sum(int(np.prod(p.shape)) * p.dtype.itemsize
                     for p in rt.cache.pools)
    assert stats.alias_size_in_bytes >= pool_bytes


# gpt2_medium's serving geometry (PERF.md section 4): hidden 1024 in 16
# heads, pages of 16 tokens, 1025 pages a pool, 64 pages a row.  The
# vocabulary is no part of the pools' geometry and is kept small, so that
# no weight is as large as one layer of a pool.
_UNITS, _HEADS, _PAGE, _PAGES, _ROW_PAGES, _SEQ, _SPEC_K = \
    1024, 16, 16, 1025, 64, 256, 3


@functools.lru_cache(maxsize=1)
def _gpt2_medium_runtime(layers, kv_dtype):
    """An unwarmed runtime of ``layers`` gpt2_medium layers.  Nothing runs
    on it, so the weights are zeros and its own cache is two pages: the
    programs take their pools' size from their arguments."""
    import mxnet_tpu as mx
    from mxnet_tpu.serving.decode import DecodeRuntime, PagedKVCache
    from mxnet_tpu.serving.decode.model import CausalLM

    net = CausalLM(vocab_size=512, units=_UNITS, num_layers=layers,
                   num_heads=_HEADS, max_length=_ROW_PAGES * _PAGE)
    for p in net.collect_params().values():
        p._load_init(mx.nd.zeros(p.shape), None)
    cache = PagedKVCache(layers, _HEADS, _UNITS // _HEADS, page_size=_PAGE,
                         num_pages=2, max_pages_per_seq=_ROW_PAGES,
                         max_slots=8, kv_dtype=kv_dtype)
    return DecodeRuntime(net, cache=cache, batch_buckets=(1, 8),
                         seq_buckets=(_SEQ,), spec_buckets=(_SPEC_K,),
                         warm=False)


def _pool_program(rt, kind, b, sds):
    """``(jitted program, its arguments with the pools last)``."""
    i32, u32, f32 = "int32", "uint32", "float32"
    blk = rt.block
    pools = tuple(sds(p.shape[:1] + (_PAGES,) + p.shape[2:], p.dtype)
                  for p in rt.cache.pools)
    if kind == "cow":
        rt.cache.warm_programs()      # builds the jit; runs on two pages
        return rt.cache._copy_fn, (sds((), i32), sds((), i32)) + pools
    params = [sds(p.shape, p.dtype) for p in rt._params]
    rows = (sds((b, _ROW_PAGES), i32), sds((b, 2), u32), sds((b,), i32),
            sds((b,), f32))           # tables, keys, steps, temps
    if kind == "step":
        return rt._build_step(), \
            (params, sds((b,), i32), sds((b,), i32)) + rows + pools
    if kind == "verify":
        return rt._build_verify(), \
            (params, sds((b, _SPEC_K + 1), i32), sds((b,), i32),
             sds((b,), i32)) + rows + pools
    kv = sds(*blk.prefill_state(b, _SEQ))
    return rt._build_commit(), \
        (params, kv, sds((b, blk.vocab_size), f32), sds((b,), i32)) \
        + rows + pools


# axk1_ep16's serving geometry (PERF.md section 4): a latent row of 512 + 64
# values in a 640-wide pool row, pages of 16 tokens, 8193 pages, 128 pages a
# row.  Every attention width is the published one; what the pool's geometry
# does not depend on (the dense FFN's width, the experts held, the
# vocabulary, the depth) is kept small so that the zeros fit a test.
_L_PAGES, _L_ROW_PAGES, _L_SEQ = 8193, 128, 512


@functools.lru_cache(maxsize=1)
def _latent_runtime():
    import mxnet_tpu as mx
    from mxnet_tpu.serving.decode import (DecodeRuntime, LatentMoELM,
                                          PagedKVCache)
    net = LatentMoELM(
        vocab_size=512, hidden_size=7168, num_layers=2, num_heads=64,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, intermediate_size=2048,
        moe_intermediate_size=2048, n_routed_experts=192,
        held_experts=(0, 1), num_experts_per_tok=8, n_group=8, topk_group=4,
        rope_scaling={"factor": 32, "original_max_position_embeddings": 4096,
                      "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                      "mscale_all_dim": 1},
        max_length=_L_ROW_PAGES * _PAGE)
    for p in net.collect_params().values():
        p._load_init(mx.nd.zeros(p.shape, dtype=p.dtype), None)
    cache = PagedKVCache(layout=net.cache_layout(), page_size=_PAGE,
                         num_pages=2, max_pages_per_seq=_L_ROW_PAGES,
                         max_slots=32)
    return DecodeRuntime(net, cache=cache, batch_buckets=(1, 32),
                         seq_buckets=(_L_SEQ,), warm=False)


def _latent_pool_program(rt, kind, b, sds):
    i32, u32, f32 = "int32", "uint32", "float32"
    blk = rt.block
    pools = tuple(sds(p.shape[:1] + (_L_PAGES,) + p.shape[2:], p.dtype)
                  for p in rt.cache.pools)
    if kind == "cow":
        rt.cache.warm_programs()
        return rt.cache._copy_fn, (sds((), i32), sds((), i32)) + pools
    params = [sds(p.shape, p.dtype) for p in rt._params]
    rows = (sds((b, _L_ROW_PAGES), i32), sds((b, 2), u32), sds((b,), i32),
            sds((b,), f32))
    if kind == "step":
        return rt._build_step(), \
            (params, sds((b,), i32), sds((b,), i32)) + rows + pools
    shape, dtype = blk.prefill_state(b, _L_SEQ)
    return rt._build_commit(), \
        (params, sds(shape, dtype), sds((b, blk.vocab_size), f32),
         sds((b,), i32)) + rows + pools


@pytest.mark.parametrize("kind,b", [("step", 1), ("step", 32),
                                    ("commit", 1), ("cow", 0)])
def test_latent_pool_programs_touch_only_their_pages(one_chip, kind, b):
    """The one latent pool (bfloat16, 640-wide rows) is held to what the
    K/V pools are: no program copies it, none slices a whole layer out of
    it, and it comes back in the buffer it was given.  Of the cached latent
    rows a step holds NOTHING (PR 38): one latent paged-attention kernel a
    layer under ``mla.attend`` reads the pages where they lie, so no array
    of ``(b, reserved context, 640)``, whole or by pages, is written, nor
    the ``(b, 64, reserved context)`` float32 scores over one: of that
    width there are the rows' folded queries and contexts ``(b, 64, 640)``
    and nothing larger."""
    import numpy as np

    rt = _latent_runtime()
    fn, args = _latent_pool_program(
        rt, kind, b, lambda shape, dtype: jax.ShapeDtypeStruct(
            tuple(shape), dtype, sharding=one_chip))
    (pool,) = args[-1:]
    assert pool.shape == (2, _L_PAGES, _PAGE, 640) and \
        pool.dtype == jnp.bfloat16
    compiled = fn.lower(*args).compile()
    what = f"latent {kind}-b{b}"
    layer = int(np.prod(pool.shape[1:]))
    reserved = _L_ROW_PAGES * _PAGE
    text = compiled.as_text()
    for op, dtype, dims in _materialised(text):
        n = int(np.prod(dims))
        if kind == "step" and (dtype, dims) != ("bf16", pool.shape):
            assert dims[-1:] != (640,) or n <= b * 64 * 640, \
                f"{what}: {op} writes {dtype}{list(dims)}: cached latent " \
                f"rows, gathered"
            assert dims[-2:] != (64, reserved) or n != b * 64 * reserved, \
                f"{what}: {op} writes {dtype}{list(dims)}: scores over a " \
                f"reserved context"
        if n < layer:
            continue
        assert (dtype, dims) == ("bf16", pool.shape), \
            f"{what}: {op} writes {dtype}{list(dims)}, a layer of the " \
            f"pool or more"
        assert op != "copy", f"{what}: copies the whole pool"
    stats = compiled.memory_analysis()
    pool_bytes = int(np.prod(pool.shape)) * 2
    assert stats.alias_size_in_bytes >= pool_bytes, what
    if kind != "step":
        assert stats.temp_size_in_bytes < pool_bytes / 2, what
        return
    paged = re.findall(r"custom_call_target=\"tpu_custom_call\""
                       r"(.*paged_latent_attention.*)$", text, re.M)
    assert len(paged) == 2 and all("/mla.attend/" in p for p in paged), \
        f"{what}: {len(paged)} latent paged-attention kernels under " \
        f"mla.attend for 2 layers"
    # the rows' vectors, queries and contexts (0.33 MB a row), weights
    # streamed ahead of their use: 4.1 MB at b = 1, 28.0 at b = 32 (sandbox
    # compiles, PR 38); a gathered context would add 2.6 MB a row a layer
    assert stats.temp_size_in_bytes < (6 << 20) + b * (1 << 20), \
        f"{what}: {stats.temp_size_in_bytes / 1e6:.1f} MB of temporaries " \
        f"beside {pool_bytes / 1e9:.3f} GB of pool"


def _materialised(hlo_text):
    """``(opcode, dtype, dims)`` of every array-valued instruction outside
    the fused computations: what the program writes to device memory."""
    fused = set(re.findall(r"\bfusion\(.*?calls=%([\w.\-]+)", hlo_text))
    out, skip = [], False
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        if head:
            skip = head.group(1) in fused
        m = re.match(r"\s+(?:ROOT )?%[\w.\-]+ = (\w+)\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(", line)
        if m and not skip:
            out.append((m.group(3), m.group(1),
                        tuple(int(d) for d in m.group(2).split(",") if d)))
    return out


_HLO_DTYPE = {"float32": "f32", "int8": "s8", "float8_e4m3fn": "f8e4m3fn"}


@pytest.mark.parametrize("kind,b,kv_dtype,layers", [
    ("step", 1, "float32", 24), ("step", 8, "float32", 24)] + [
    (kind, b, kv_dtype, 4)
    for kv_dtype in ("float32", "int8", "fp8_e4m3")
    for kind, b in (("step", 1), ("step", 8), ("verify", 4), ("commit", 1),
                    ("cow", 0))
    if (kind, kv_dtype) != ("step", "float32")])
def test_pool_programs_touch_only_their_pages(one_chip, kind, b, kv_dtype,
                                              layers):
    """Every program that takes the donated KV pools updates them in the
    buffers it was given: none copies a pool into another layout, none
    slices a whole layer out of one, and its temporaries are small beside
    the pools."""
    import numpy as np

    rt = _gpt2_medium_runtime(layers, kv_dtype)
    fn, args = _pool_program(
        rt, kind, b, lambda shape, dtype: jax.ShapeDtypeStruct(
            tuple(shape), dtype, sharding=one_chip))
    pools = args[-len(rt.cache.pools):]
    compiled = fn.lower(*args).compile()
    what = f"{kind}-b{b} {kv_dtype} x{layers}"

    kv_shapes = {(_HLO_DTYPE[str(p.dtype)], p.shape) for p in pools[:2]}
    layer = int(np.prod(pools[0].shape[1:]))
    for op, dtype, dims in _materialised(compiled.as_text()):
        if int(np.prod(dims)) < layer:
            continue
        # as large as a layer of a pool: only the pool itself may be,
        # updated where it lies (the memory numbers below hold it to that)
        assert (dtype, dims) in kv_shapes, \
            f"{what}: {op} writes {dtype}{list(dims)}, a layer of a pool " \
            f"or more"
        assert op != "copy", f"{what}: copies a whole pool {dtype}{dims}"

    stats = compiled.memory_analysis()
    pool_bytes = sum(int(np.prod(p.shape)) * p.dtype.itemsize for p in pools)
    assert stats.alias_size_in_bytes >= pool_bytes, what
    assert stats.temp_size_in_bytes < pool_bytes / 10, \
        f"{what}: {stats.temp_size_in_bytes / 1e9:.3f} GB of temporaries " \
        f"beside {pool_bytes / 1e9:.3f} GB of pools"


def test_a_scripted_run_compiles_nothing_after_warm():
    """The run-time half of "one step program a batch bucket" (ISSUE 36): a
    step takes its tokens from the host in a synchronous turn and from the
    step before it, still on the device, in a turn launched ahead.  After
    ``warm()`` a scripted run with joins and finishes across two buckets
    counts no ``decode.compile_miss`` and no compile of the backend, and
    each bucket's program has ONE executable for both sides.  (Runs on the
    CPU: what is guarded is the program's signature, not a kernel.)"""
    import numpy as np
    from mxnet_tpu import telemetry
    from mxnet_tpu.serving.decode import (DecodeRuntime, DecodeScheduler,
                                          get_decode_model)
    net = get_decode_model("decode_tiny", vocab_size=61, max_length=32,
                           units=32, num_heads=2)
    net.initialize()
    rt = DecodeRuntime(net, batch_buckets=(2, 4), seq_buckets=(8,),
                       page_size=8, prefix_sharing=False)
    compiles, sides = [], set()
    launch = rt.launch

    def on(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    def seen(tokens, *rest):
        sides.add((tokens.shape[0], isinstance(tokens, np.ndarray)))
        return launch(tokens, *rest)

    rt.launch = seen
    telemetry.enable()
    telemetry.reset()
    jax.monitoring.register_event_duration_secs_listener(on)
    s = DecodeScheduler(rt, start=False)
    try:
        futs = [s.submit([3 + i, 7, 11], max_new_tokens=9, seed=i)
                for i in range(2)]
        for _ in range(3):
            s._boundary()
        futs += [s.submit([5 + i, 2], max_new_tokens=3, seed=9 + i)
                 for i in range(2)]                 # two join: four rows
        for _ in range(40):
            if not s._running():
                break
            s._boundary()                           # two finish: two rows
        assert [len(f.result(0).token_ids) for f in futs] == [9, 9, 3, 3]
        counters = telemetry.snapshot()["counters"]
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
        telemetry.disable()
        telemetry.reset()
        s.close(drain=False, timeout=10.0)
    assert sides == {(b, host) for b in (2, 4) for host in (True, False)}
    assert not counters.get("decode.compile_miss") and not compiles
    assert counters["decode.steps_ahead"] >= 4
    assert sorted(rt._step_fns) == [2, 4]
    assert [fn._cache_size() for fn in rt._step_fns.values()] == [1, 1]



# nemotron3_nano_ep8's serving geometry (PERF.md section 4): per Mamba layer
# and slot a recurrent state of 64 x 64 x 128 float32 values (2 MB) and a
# convolution tail of 3 x 6144 bfloat16, 32 slots and the trash row; K/V rows
# of 2 x 128 in pages of 16, 96 pages a row.  Every width of the mixers and
# of attention is the published one; what the pools' geometry does not depend
# on (the experts held, the vocabulary, the depth) is kept small so that the
# zeros fit a test.
_H_PAGES, _H_ROW_PAGES, _H_SLOTS, _H_SEQ = 6145, 96, 32, 256


@functools.lru_cache(maxsize=1)
def _hybrid_runtime():
    import mxnet_tpu as mx
    from mxnet_tpu.serving.decode import (DecodeRuntime, HybridSSMMoELM,
                                          PagedKVCache)
    net = HybridSSMMoELM(
        vocab_size=512, hidden_size=2688, pattern="M*EM*M",
        mamba_num_heads=64, mamba_head_dim=64, ssm_state_size=128,
        n_groups=8, conv_kernel=4, chunk_size=128, num_attention_heads=32,
        num_key_value_heads=2, head_dim=128, moe_intermediate_size=1856,
        moe_shared_expert_intermediate_size=3712, n_routed_experts=128,
        held_experts=(0, 1), num_experts_per_tok=6,
        max_length=_H_ROW_PAGES * _PAGE)
    for p in net.collect_params().values():
        p._load_init(mx.nd.zeros(p.shape, dtype=p.dtype), None)
    cache = PagedKVCache(layout=net.cache_layout(), page_size=_PAGE,
                         num_pages=2, max_pages_per_seq=_H_ROW_PAGES,
                         max_slots=1)
    return DecodeRuntime(net, cache=cache, batch_buckets=(1,),
                         seq_buckets=(_H_SEQ,), warm=False)


def _hybrid_program(rt, kind, b, sds):
    """``(jitted program, its arguments with the pools last)``; the pools at
    the cell's size: 6,145 pages, 33 state rows."""
    i32, u32, f32 = "int32", "uint32", "float32"
    blk, n_paged = rt.block, len(rt.cache.pool_layout)
    pools = tuple(
        sds(p.shape[:1] + ((_H_PAGES,) if j < n_paged else (_H_SLOTS + 1,))
            + p.shape[2:], p.dtype) for j, p in enumerate(rt.cache.pools))
    params = [sds(p.shape, p.dtype) for p in rt._params]
    if kind == "prefill":
        fn = jax.jit(lambda leaves, tok, ln: blk.prefill_math(
            blk._params_dict(leaves), tok, ln))
        return fn, (params, sds((b, _H_SEQ), i32), sds((b,), i32)), pools
    rows = (sds((b, _H_ROW_PAGES + 1), i32), sds((b, 2), u32),
            sds((b,), i32), sds((b,), f32))     # tables, keys, steps, temps
    if kind == "step":
        return rt._build_step(), \
            (params, sds((b,), i32), sds((b,), i32)) + rows + pools, pools
    state = tuple(sds(shape, dtype)
                  for shape, dtype in blk.prefill_state(b, _H_SEQ))
    return rt._build_commit(), \
        (params, state, sds((b, blk.vocab_size), f32), sds((b,), i32)) \
        + rows + pools, pools


_SSM_CALL = re.compile(
    r"%([\w.\-]+) = \(f32\[3,33,64,64,128\]\S*, f32\[\d+,64,64\]\S*\) "
    r"custom-call\(((?:%[\w.\-]+(?:, )?|/\*index=\d+\*/)+)\), "
    r"custom_call_target=\"tpu_custom_call\"(.*)$", re.M)


_PAGED_CALL = re.compile(
    r"%([\w.\-]+) = f32\[\d+,\d+,128\]\S* custom-call\(.*"
    r"custom_call_target=\"tpu_custom_call\"(.*paged_attention.*)$", re.M)


def _paged_calls(hlo_text):
    """``(name, the rest of its line)`` of every paged-attention kernel
    (``paged_attention``) in a compiled step."""
    return [(m.group(1), m.group(2)) for m in _PAGED_CALL.finditer(hlo_text)]


def _ssm_calls(hlo_text):
    """``(name, operands, the rest of its line)`` of every recurrence kernel
    (``ssm_step_slots``) in a compiled step, in program order."""
    return [(m.group(1), re.findall(r"%([\w.\-]+)", m.group(2)), m.group(3))
            for m in _SSM_CALL.finditer(hlo_text)]


@pytest.mark.parametrize("kind,b", [("step", 1), ("step", 8), ("step", 32),
                                    ("commit", 1), ("prefill", 1)])
def test_hybrid_programs_touch_only_their_slots_and_pages(one_chip, kind, b):
    """The state pools (23 x 33 x 2 MB at the cell's depth; three Mamba
    layers here) are held to what the page pools are: no step, commit or
    prefill program of the hybrid block holds a temporary the size of a
    state pool or of one layer of it, none copies a pool, and the step and
    commit give every pool back in the buffer it came in.  Every step
    program, whatever its batch, advances the recurrence in ONE kernel a
    Mamba layer (``ssm_step_slots``, under ``ssm.mix``), which moves its
    rows' states between the pool and the core's own memory: the program's
    temporaries hold no row's state, gathered or otherwise."""
    import numpy as np

    rt = _hybrid_runtime()
    fn, args, pools = _hybrid_program(
        rt, kind, b, lambda shape, dtype: jax.ShapeDtypeStruct(
            tuple(shape), dtype, sharding=one_chip))
    k_pool, _v, ssm_pool, conv_pool = pools
    assert ssm_pool.shape == (3, 33, 64, 64, 128) and \
        ssm_pool.dtype == jnp.float32
    assert conv_pool.shape == (3, 33, 144, 128) and \
        k_pool.shape == (2, _H_PAGES, _PAGE, 256)
    compiled = fn.lower(*args).compile()
    what = f"hybrid {kind}-b{b}"
    own = {("f32", ssm_pool.shape), ("bf16", conv_pool.shape),
           ("bf16", k_pool.shape)}
    layer = int(np.prod(ssm_pool.shape[1:]))
    # at 2688 wide a weight matrix is as large as a layer of state: the
    # parameters, and the copies of one that the compiler streams ahead of
    # its use, are not what is looked for
    weights = {tuple(p.shape) for p in args[0]}
    text = compiled.as_text()
    for op, dtype, dims in _materialised(text):
        if int(np.prod(dims)) < layer or dims in weights or \
                op in ("parameter", "get-tuple-element", "bitcast"):
            continue
        assert (dtype, dims) in own, \
            f"{what}: {op} writes {dtype}{list(dims)}, a layer of the " \
            f"state pool or more"
        assert op != "copy", f"{what}: copies a whole pool {dtype}{dims}"
    stats = compiled.memory_analysis()
    pool_bytes = sum(int(np.prod(p.shape)) * p.dtype.itemsize for p in pools)
    calls = _ssm_calls(text)
    if kind == "prefill":
        # one prompt of 256: its two chunks' states, decay matrices and
        # scores, small beside one layer of 33 slots
        assert stats.temp_size_in_bytes < layer * 4, what
        assert not calls, what
        return
    assert stats.alias_size_in_bytes >= pool_bytes, what
    if kind == "commit":
        assert not calls, what
        return
    assert len(calls) == 3, f"{what}: {len(calls)} recurrence kernels for " \
        f"3 Mamba layers"
    for _name, _operands, rest in calls:
        assert "/ssm.mix/" in rest and "ssm_step_slots" in rest, \
            f"{what}: the kernel left its scope: {rest[:300]}"
    # the two grouped-query layers attend in ONE kernel each over the pages
    # where they lie (PR 34): no row's reserved context (1,536 tokens of
    # 256 keys or values) is gathered, whole or by pages
    paged = _paged_calls(text)
    assert len(paged) == 2 and all("/attn.gqa/" in rest
                                   for _name, rest in paged), \
        f"{what}: {len(paged)} paged-attention kernels under attn.gqa for " \
        f"2 grouped-query layers"
    for op, dtype, dims in _materialised(text):
        assert _H_ROW_PAGES * _PAGE not in dims and \
            dims[-3:-1] != (_H_ROW_PAGES, _PAGE), \
            f"{what}: {op} writes {dtype}{list(dims)}: a reserved context"
    # the rows' vectors and weights streamed ahead of their use; ONE row's
    # state of one layer is 2 MB and does not fit beside them, nor does one
    # row's paged context of one layer, 1.5 MB (6.1 MB at b = 1, 7.7 at b =
    # 32; sandbox compiles, PR 34.  With the context gathered it was 6.2 and
    # 7.0 + 0.4 a row, PR 31; with the states gathered too, 64 MB and more)
    assert stats.temp_size_in_bytes < (7 << 20) + b * (1 << 15), \
        f"{what}: {stats.temp_size_in_bytes / 1e6:.1f} MB of temporaries " \
        f"beside {pool_bytes / 1e9:.3f} GB of pools"


def test_hybrid_step_kernels_work_in_the_donated_state_pool(one_chip):
    """The recurrent-state pool goes from the program's donated parameter
    through the three layers' kernels to the program's result as ONE
    buffer: the first kernel's pool operand is the parameter itself, each
    later one's is the pool the kernel before it returned, each call
    aliases that operand to its first result, the program's result is the
    last kernel's, and the module aliases that result to the parameter."""
    rt = _hybrid_runtime()
    fn, args, pools = _hybrid_program(
        rt, "step", 32, lambda shape, dtype: jax.ShapeDtypeStruct(
            tuple(shape), dtype, sharding=one_chip))
    text = fn.lower(*args).compile().as_text()
    calls = _ssm_calls(text)
    assert len(calls) == 3
    param = re.search(r"%([\w.\-]+) = f32\[3,33,64,64,128\]\S* "
                      r"parameter\((\d+)\)", text)
    pool, number = param.group(1), int(param.group(2))
    for name, operands, rest in calls:
        # four scalars come first: the layer, the state row each grid step
        # names, the rows' own state rows; the decays
        assert operands[4] == pool, \
            f"{name} reads its pool from %{operands[4]}, not %{pool}"
        assert "output_to_operand_aliasing={{0}: (4, {})}" in rest, name
        got = re.search(r"%([\w.\-]+) = f32\[3,33,64,64,128\]\S* "
                        r"get-tuple-element\(%" + re.escape(name)
                        + r"\), index=0", text)
        pool = got.group(1)
    root = re.search(r"ROOT %[\w.\-]+ = \(.*\) tuple\((.*?)\)",
                     text[text.index("ENTRY"):])
    out = [o.strip().lstrip("%") for o in
           re.sub(r"/\*index=\d+\*/", "", root.group(1)).split(",")]
    n_paged = len(rt.cache.pool_layout)
    assert out[1 + n_paged] == pool
    assert f"{{{1 + n_paged}}}: ({number}, {{}}" in text.splitlines()[0]


def test_hybrid_step_for_the_chip_counts_the_kernel_once_a_mamba_layer(
        one_chip):
    """``ssm.step.path``: lowering the 32-row step for the described chip
    counts ``kind="kernel"`` once a Mamba layer and ``plain`` never (for
    the CPU it is the other way round: ``tests/test_ssm_step_kernel.py``).
    Both forms are traced; the count is made when one is lowered."""
    from mxnet_tpu.test_utils import counted
    rt = _hybrid_runtime()
    fn, args, _pools = _hybrid_program(
        rt, "step", 32, lambda shape, dtype: jax.ShapeDtypeStruct(
            tuple(shape), dtype, sharding=one_chip))
    assert counted("ssm.step.path", lambda: fn.lower(*args)) == \
        {'{kind="kernel",rows="32"}': 3}


@pytest.mark.parametrize("block", ["hybrid", "window", "latent"])
def test_step_counts_the_paged_kernel_once_a_paged_layer(one_chip, block):
    """``decode.attn.paged.lowered``: lowering the 32-row step for the
    described chip counts ``kind="kernel"`` once a layer that pages (two
    grouped-query layers here, one global layer there, both layers of the
    latent block) and ``plain`` never; lowering the same program for the
    CPU, the other way round.  Nothing but the platform differs between the
    two."""
    from mxnet_tpu.test_utils import counted
    rt, program, layers = {
        "hybrid": (_hybrid_runtime, _hybrid_program, 2),
        "window": (_window_runtime, _window_program, 1),
        "latent": (_latent_runtime, _latent_pool_program, 2)}[block]
    for sharding, kind in ((one_chip, "kernel"), (None, "plain")):
        fn, args, *_pools = program(
            rt(), "step", 32, lambda shape, dtype: jax.ShapeDtypeStruct(
                tuple(shape), dtype, sharding=sharding))
        assert counted("decode.attn.paged.lowered",
                       lambda: fn.lower(*args)) == \
            {f'{{kind="{kind}",rows="32"}}': layers}


@pytest.mark.parametrize("rows", [32, 2048])
def test_hyper_connections_compile_as_a_loop_for_v5e(one_chip, rows):
    """One sublayer's mixing at Xing4.0's widths (4 streams of 3,584), for a
    step's rows and a prefill's tokens: the 20 Sinkhorn rounds stay ONE loop
    on the device (unrolled they are 2.6 s of compiling a sublayer, twelve
    minutes a 2,048-token prefill program of 80), the streams keep a layout
    without padding (the 4-wide axis is not tiled to 8), and nothing is a
    reduction of the whole stream in float64 or a copy of it."""
    from mxnet_tpu.ops import hyper_connection as hc
    n, C = 4, 3584

    def sublayer(X, phi, a, b, y):
        h_pre, h_post, h_res = hc.hc_coefficients(
            X, {"phi": phi, "a": a, "b": b}, 20, 1e-6, (-30.0, 30.0))
        return hc.hc_write(X, h_res, h_post, hc.hc_read(X, h_pre) * y)

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    text = jax.jit(sublayer).lower(
        sds(rows, n, C), sds(n * C, n * (n + 2)), sds(3), sds(n * (n + 2)),
        sds(rows, C)).compile().as_text()
    assert len(re.findall(r" while\(", text)) == 1
    assert len(re.findall(r" fusion\(", text)) < 40
    padded = re.findall(rf"f32\[{rows},4,3584\]\{{2,1,0:T\(8,128\)", text)
    assert not padded, padded[:2]


def _hyper_block(hc_mult=4):
    """A declared (no array) one-layer ``LatentMoELM`` at Xing4.0's hidden
    width: ``_sublayer`` is what is asked about."""
    from mxnet_tpu.serving.decode import LatentMoELM
    return LatentMoELM(vocab_size=512, hidden_size=3584, num_layers=1,
                       num_heads=4, first_k_dense_replace=1,
                       intermediate_size=256, hc_mult=hc_mult)


@pytest.mark.parametrize("rows", [32, 2048])
def test_hyper_connections_are_two_kernels_a_sublayer_for_v5e(one_chip, rows):
    """The same two shapes through the path the chip runs
    (``LatentMoELM._sublayer`` -> ``by_platform``): compiled for the
    described chip one sublayer's mixing is two Mosaic calls, ``hc_pre``
    under ``hc.coef`` and ``hc_post`` under ``hc.mix``, with no loop and next
    to no fusion around them; neither the streams nor ``phi`` lie padded on
    their 4- or 24-wide axis (``phi`` arrives with its long axis minor, the
    streams as rows of ``4 x 3,584`` lanes); the written streams take the
    buffer of the ones read; and ``decode.hc.lowered`` counts
    ``kind="kernel"`` once a half."""
    from mxnet_tpu.test_utils import counted
    net, n, C = _hyper_block(), 4, 3584

    def sublayer(X, phi_t, a, b, gain, y):
        p = {"l0_hc_attn_phi": phi_t, "l0_hc_attn_a": a, "l0_hc_attn_b": b,
             "l0_norm_attn": gain}
        return net._sublayer(p, 0, "attn", X, lambda m: (m * y,))

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    args = (sds(rows, n, C), sds(n * (n + 2), n * C), sds(3),
            sds(n * (n + 2)), sds(C), sds(rows, C))
    fn = jax.jit(sublayer, donate_argnums=0)
    assert counted("decode.hc.lowered", lambda: fn.lower(*args)) == \
        {f'{{kind="kernel",tokens="{rows}"}}': 2}
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    calls = re.findall(r"custom_call_target=\"tpu_custom_call\"(.*)$", text,
                       re.M)
    assert len(calls) == 2 and "/hc.coef/" in calls[0] \
        and "hc_pre" in calls[0] and "/hc.mix/" in calls[1] \
        and "hc_post" in calls[1], calls
    assert not re.findall(r" while\(", text)
    assert len(re.findall(r" fusion\(", text)) < 10
    padded = re.findall(
        rf"f32\[(?:{rows},4,3584|14336,24|{rows},4|{rows},4,4)\]\S*T\(8,128\)",
        text)
    assert not padded, padded[:2]
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        rows * n * C * 4


def test_plain_residual_programs_name_no_hyper_connection_kernel(one_chip):
    """``hc_mult == 1`` returns before any of it: the 32-row step and the
    prefill of the latent block lower, for the described chip, to text that
    names no ``hc`` kernel or scope and no ``decode.hc.lowered`` count, and
    that is, character for character, the text they lower to with
    ``_sublayer`` put back to the three lines it was before hyper-connections
    (``h + part`` for each part of ``f(norm(h))``)."""
    from mxnet_tpu.serving.decode import latent_moe
    from mxnet_tpu.test_utils import counted
    rt = _latent_runtime()
    blk = rt.block
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(tuple(shape), dtype,
                                                    sharding=one_chip)

    def texts():
        fn, args = _latent_pool_program(rt, "step", 32, sds)
        prefill = jax.jit(lambda leaves, tok, ln: blk.prefill_math(
            blk._params_dict(leaves), tok, ln))
        return (fn.lower(*args).as_text(), prefill.lower(
            [sds(p.shape, p.dtype) for p in rt._params],
            sds((1, 256), "int32"), sds((1,), "int32")).as_text())

    got = []
    assert counted("decode.hc.lowered", lambda: got.extend(texts())) == {}

    def before(self, p, i, sub, h, f, resid=None, live=None):
        for part in f(latent_moe._rms(h, p[f"l{i}_norm_{sub}"], self.eps)):
            h = h + part
        return h

    was = latent_moe.LatentMoELM._sublayer
    latent_moe.LatentMoELM._sublayer = before
    try:
        rt._step_fns.clear()
        want = texts()
    finally:
        latent_moe.LatentMoELM._sublayer = was
        rt._step_fns.clear()
    for text, ref in zip(got, want):
        assert "hc_pre" not in text and "hc_post" not in text \
            and "hc." not in text
        assert text == ref


# ------------------------------------------- the window / global block's
# the cell's geometry (mimo_v2_5_ep16.mixed_lengths): 18,433 pages, 288 a
# row (4,608 tokens), 33 rings a window layer, the longest prefill bucket
_W_PAGES, _W_ROW_PAGES, _W_SLOTS, _W_SEQ = 18433, 288, 32, 4096


@functools.lru_cache(maxsize=1)
def _window_runtime():
    """MiMo-V2.5's attention at every published width (64 query heads, 4
    global and 8 window K/V heads, keys 192 wide over values 128, a window
    of 128) in a global + dense layer and two window + expert layers; two
    held experts and a small vocabulary, which are not what is asked
    about."""
    import mxnet_tpu as mx
    from mxnet_tpu.serving.decode import (DecodeRuntime, PagedKVCache,
                                          WindowMoELM)
    net = WindowMoELM(
        vocab_size=512, hidden_size=4096, layer_pattern=(0, 1, 1),
        moe_layer_freq=(0, 1, 1), num_attention_heads=64,
        num_key_value_heads=4, swa_num_key_value_heads=8, head_dim=192,
        v_head_dim=128, sliding_window=128, intermediate_size=2048,
        moe_intermediate_size=2048, n_routed_experts=256,
        held_experts=(0, 1), num_experts_per_tok=8,
        max_length=_W_ROW_PAGES * _PAGE)
    for p in net.collect_params().values():
        p._load_init(mx.nd.zeros(p.shape, dtype=p.dtype), None)
    cache = PagedKVCache(layout=net.cache_layout(), page_size=_PAGE,
                         num_pages=2, max_pages_per_seq=_W_ROW_PAGES,
                         max_slots=1)
    return DecodeRuntime(net, cache=cache, batch_buckets=(1,),
                         seq_buckets=(_W_SEQ,), warm=False)


def _window_program(rt, kind, b, sds):
    """``(jitted program, its arguments with the pools last, the pools)``;
    the pools at the cell's size."""
    i32, u32, f32 = "int32", "uint32", "float32"
    blk, n_paged = rt.block, len(rt.cache.pool_layout)
    pools = tuple(
        sds(p.shape[:1] + ((_W_PAGES,) if j < n_paged else (_W_SLOTS + 1,))
            + p.shape[2:], p.dtype) for j, p in enumerate(rt.cache.pools))
    params = [sds(p.shape, p.dtype) for p in rt._params]
    if kind == "prefill":
        fn = jax.jit(lambda leaves, tok, ln: blk.prefill_math(
            blk._params_dict(leaves), tok, ln))
        return fn, (params, sds((b, _W_SEQ), i32), sds((b,), i32)), pools
    rows = (sds((b, _W_ROW_PAGES + 1), i32), sds((b, 2), u32),
            sds((b,), i32), sds((b,), f32))     # tables, keys, steps, temps
    if kind == "step":
        return rt._build_step(), \
            (params, sds((b,), i32), sds((b,), i32)) + rows + pools, pools
    state = tuple(sds(shape, dtype)
                  for shape, dtype in blk.prefill_state(b, _W_SEQ))
    return rt._build_commit(), \
        (params, state, sds((b, blk.vocab_size), f32), sds((b,), i32)) \
        + rows + pools, pools


def test_window_prefill_holds_no_heads_by_s_by_s_array(one_chip):
    """A prompt of 4,096: the float32 scores of ONE layer as a ``(heads, S,
    S)`` array would be 64 x 4,096^2 x 4 B = 4.3 GB.  The program goes by
    query blocks of the window: its largest array is a global layer's
    scores of one block over all keys (64 x 128 x 4,096 float32, 134 MB) or
    a window layer's band (64 x 4,096 x 256, 268 MB), and all its
    temporaries together are a quarter of that one array."""
    import numpy as np
    rt = _window_runtime()
    fn, args, _pools = _window_program(
        rt, "prefill", 1, lambda shape, dtype: jax.ShapeDtypeStruct(
            tuple(shape), dtype, sharding=one_chip))
    compiled = fn.lower(*args).compile()
    heads, S = 64, _W_SEQ
    for op, dtype, dims in _materialised(compiled.as_text()):
        assert int(np.prod(dims)) < heads * S * S // 8, \
            f"window prefill: {op} writes {dtype}{list(dims)}"
        # (S, hidden) is 4,096 x 4,096 here too: an S x S array of scores
        # has a heads axis beside them
        assert len(dims) < 3 or sum(d == S for d in dims) < 2, \
            f"window prefill: {op} writes {dtype}{list(dims)}: S x S"
    assert compiled.memory_analysis().temp_size_in_bytes < \
        heads * S * S * 4 // 4


@pytest.mark.parametrize("kind,b", [("step", 1), ("step", 32),
                                    ("commit", 1)])
def test_window_programs_touch_only_their_rings_and_pages(one_chip, kind, b):
    """Two kinds of attention state in one donated tuple.  No step or
    commit program copies a pool or holds a temporary the size of one layer
    of the page pools, and each gives every pool back in the buffer it came
    in.  Of a window layer's K/V a step holds the rings of its ``b`` rows
    (``b x 128`` tokens, gathered by state row) and nothing beyond: no
    array with a window layer's row (8 heads: 1,536 keys, 1,024 values) has
    more tokens than that, whatever the 4,608 the context allows.  Of the
    global layer's K/V a step holds NOTHING (PR 34): one paged-attention
    kernel under ``attn.global`` reads the pages where they lie, and no
    array of ``(b, reserved context, 768 | 512)``, whole or by pages, is
    written."""
    import numpy as np
    rt = _window_runtime()
    fn, args, pools = _window_program(
        rt, kind, b, lambda shape, dtype: jax.ShapeDtypeStruct(
            tuple(shape), dtype, sharding=one_chip))
    k_pool, v_pool, ring_k, ring_v = pools
    assert k_pool.shape == (1, _W_PAGES, _PAGE, 768) and \
        v_pool.shape == (1, _W_PAGES, _PAGE, 512)
    assert ring_k.shape == (2, 33, 128, 1536) and \
        ring_v.shape == (2, 33, 128, 1024)
    compiled = fn.lower(*args).compile()
    what = f"window {kind}-b{b}"
    # a pool updated where it lies, under whatever shape the compiler
    # gives the one page layer here ((18433, 16, 768), (294928, 768))
    own = {int(np.prod(p.shape)) for p in pools}
    weights = {tuple(p.shape) for p in args[0]}
    page_layer = int(np.prod(v_pool.shape[1:]))
    window = 128
    text = compiled.as_text()
    for op, dtype, dims in _materialised(text):
        if op in ("parameter", "get-tuple-element", "bitcast") or \
                dims in weights:
            continue
        n = int(np.prod(dims))
        if kind == "step":
            assert _W_ROW_PAGES * _PAGE not in dims and \
                dims[-3:-1] != (_W_ROW_PAGES, _PAGE), \
                f"{what}: {op} writes {dtype}{list(dims)}: a reserved " \
                f"context"
        if dtype == "bf16" and n in own:
            assert op != "copy", f"{what}: copies a whole pool {dtype}{dims}"
            continue
        assert n < page_layer, \
            f"{what}: {op} writes {dtype}{list(dims)}, a layer of a page " \
            f"pool or more"
        # (two axes: a row's vector, or a slice of the projection's weight
        # streamed ahead of its use)
        if kind == "step" and len(dims) > 2 and dtype == "bf16" and \
                dims[-1] in (1536, 1024):
            # (at b = 1 the compiler stages ONE layer's 33 rings, 8.6 MB,
            # ahead of the row's gather: never more than a layer's rings)
            assert n <= max(b, _W_SLOTS + 1) * window * dims[-1], \
                f"{what}: {op} writes {dtype}{list(dims)}: more of a " \
                f"window layer's K/V than {b} rings"
    stats = compiled.memory_analysis()
    pool_bytes = sum(int(np.prod(p.shape)) * p.dtype.itemsize for p in pools)
    assert stats.alias_size_in_bytes >= pool_bytes, what
    if kind == "commit":
        assert stats.temp_size_in_bytes < (8 << 20), what
        return
    paged = _paged_calls(text)
    assert len(paged) == 1 and "/attn.global/" in paged[0][1], \
        f"{what}: {len(paged)} paged-attention kernels under attn.global " \
        f"for 1 global layer"
    # the b rings of a window layer (0.66 MB a row), the rows' vectors,
    # weights streamed ahead of their use: 5.0 MB at b = 1, 17.8 at b = 32
    # (sandbox compiles, PR 34).  Until PR 34 the paged context of the b rows
    # was gathered whole (ROADMAP S2, delivered): 534 MB at b = 32, 377 of
    # them the context (4,608 x (768 + 512) bf16 a row)
    assert stats.temp_size_in_bytes < (6 << 20) + b * (1 << 19), \
        f"{what}: {stats.temp_size_in_bytes / 1e6:.1f} MB of temporaries " \
        f"beside {pool_bytes / 1e9:.3f} GB of pools"


# ------------------------------------- the linear-attention / gated GQA block's
# the cell's geometry (solar_open2_ep16.reason_long): 10,241 pages, 320 a row
# (5,120 tokens), 33 state rows a KDA layer, the longest prefill bucket
_K_PAGES, _K_ROW_PAGES, _K_SLOTS, _K_SEQ = 10241, 320, 32, 2048


@pytest.mark.parametrize("b", [1, 32])
def test_kda_step_kernel_compiles_for_v5e(one_chip, b):
    """``kda_step_slots`` at Solar-Open2's widths (64 heads of 128 x 128, 4
    MB a row's state of one layer, four such blocks in the core's memory)
    over the cell's state pool of six layers and 33 state rows: one Mosaic
    call that gives the pool back in the buffer it came in."""
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    pool = sds((6, _K_SLOTS + 1, 64, 128, 128), "float32")
    row = sds((b, 64, 128), "float32")
    fn = jax.jit(lambda pool, rows, q, k, v, g, beta:
                 pallas_kernels.kda_step_slots(pool, 3, rows, q, k, v, g,
                                               beta), donate_argnums=0)
    compiled = fn.lower(pool, sds((b,), "int32"), row, row, row, row,
                        sds((b, 64), "float32")).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "kda_step_slots" in text
    assert "output_to_operand_aliasing={{0}: (4, {})}" in text
    # nothing the size of a row's state beside the pool: the kernel moves
    # states between the pool and the core's own memory
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


@functools.lru_cache(maxsize=1)
def _linear_runtime():
    """Solar-Open2's mixers at every published width (KDA: 64 heads of 128,
    4 taps, gates of rank 128; grouped-query: 64 queries over 8 K/V heads
    of 128 with the output gate) in one grouped-query layer and two KDA
    layers, each with its expert sublayer at width 1280; two held experts
    and a small vocabulary, which are not what is asked about."""
    import mxnet_tpu as mx
    from mxnet_tpu.serving.decode import (DecodeRuntime, LinearMoELM,
                                          PagedKVCache)
    net = LinearMoELM(
        vocab_size=512, hidden_size=4096, num_layers=3, gqa_layers=(0,),
        num_attention_heads=64, num_key_value_heads=8, head_dim=128,
        kda_num_heads=64, kda_head_dim=128, conv_kernel=4, gate_rank=128,
        chunk_size=64, moe_intermediate_size=1280, n_routed_experts=320,
        held_experts=(0, 1), num_experts_per_tok=8,
        max_length=_K_ROW_PAGES * _PAGE)
    for p in net.collect_params().values():
        p._load_init(mx.nd.zeros(p.shape, dtype=p.dtype), None)
    cache = PagedKVCache(layout=net.cache_layout(), page_size=_PAGE,
                         num_pages=2, max_pages_per_seq=_K_ROW_PAGES,
                         max_slots=1)
    return DecodeRuntime(net, cache=cache, batch_buckets=(1,),
                         seq_buckets=(_K_SEQ,), warm=False)


def _linear_program(rt, kind, b, sds):
    """``(jitted program, its arguments with the pools last, the pools)``;
    the pools at the cell's size."""
    i32, u32, f32 = "int32", "uint32", "float32"
    blk, n_paged = rt.block, len(rt.cache.pool_layout)
    pools = tuple(
        sds(p.shape[:1] + ((_K_PAGES,) if j < n_paged else (_K_SLOTS + 1,))
            + p.shape[2:], p.dtype) for j, p in enumerate(rt.cache.pools))
    params = [sds(p.shape, p.dtype) for p in rt._params]
    if kind == "prefill":
        fn = jax.jit(lambda leaves, tok, ln: blk.prefill_math(
            blk._params_dict(leaves), tok, ln))
        return fn, (params, sds((b, _K_SEQ), i32), sds((b,), i32)), pools
    rows = (sds((b, _K_ROW_PAGES + 1), i32), sds((b, 2), u32),
            sds((b,), i32), sds((b,), f32))     # tables, keys, steps, temps
    if kind == "step":
        return rt._build_step(), \
            (params, sds((b,), i32), sds((b,), i32)) + rows + pools, pools
    state = tuple(sds(shape, dtype)
                  for shape, dtype in blk.prefill_state(b, _K_SEQ))
    return rt._build_commit(), \
        (params, state, sds((b, blk.vocab_size), f32), sds((b,), i32)) \
        + rows + pools, pools


_KDA_CALL = re.compile(
    r"%([\w.\-]+) = \(f32\[2,33,64,128,128\]\S*, f32\[\d+,64,128\]\S*\) "
    r"custom-call\(.*custom_call_target=\"tpu_custom_call\"(.*)$", re.M)


def test_linear_prefill_holds_no_heads_by_s_by_s_array(one_chip):
    """A prompt of 2,048: the float32 scores of the grouped-query layer as a
    ``(heads, S, S)`` array would be 64 x 2,048^2 x 4 B = 1.07 GB, and the
    in-chunk decay of ONE KDA layer as ``(heads, chunks, C, C, dk)`` 2.1 GB.
    The program goes by query blocks of 256 (scores of 64 x 256 x 2,048,
    134 MB) and forms the in-chunk matrices by sub-chunks: its largest array
    is the three convolutions' input side by side (2,048 x 24,576 float32,
    201 MB), no array is a quarter of either, and the recurrence is XLA's
    (no kernel in prefill)."""
    import numpy as np
    rt = _linear_runtime()
    fn, args, _pools = _linear_program(
        rt, "prefill", 1, lambda shape, dtype: jax.ShapeDtypeStruct(
            tuple(shape), dtype, sharding=one_chip))
    compiled = fn.lower(*args).compile()
    heads, S = 64, _K_SEQ
    text = compiled.as_text()
    for op, dtype, dims in _materialised(text):
        assert int(np.prod(dims)) < heads * S * S // 4, \
            f"linear prefill: {op} writes {dtype}{list(dims)}"
        assert len(dims) < 3 or sum(d == S for d in dims) < 2, \
            f"linear prefill: {op} writes {dtype}{list(dims)}: S x S"
    assert not _KDA_CALL.search(text)
    assert compiled.memory_analysis().temp_size_in_bytes < \
        heads * S * S * 4


@pytest.mark.parametrize("kind,b", [("step", 1), ("step", 32),
                                    ("commit", 1)])
def test_linear_programs_touch_only_their_slots_and_pages(one_chip, kind, b):
    """The matrix-state pool (6 x 33 x 4.19 MB at the cell's depth; two KDA
    layers here) is held to what the page pools are: no step or commit
    program copies a pool or holds a temporary the size of one layer of the
    state pool, and each gives every pool back in the buffer it came in.
    Every step program, whatever its batch, advances the recurrence in ONE
    kernel a KDA layer (``kda_step_slots``, under ``kda.recur``) and
    attends in ONE paged-attention kernel under ``attn.gqa``: the program's
    temporaries hold no row's state and no row's context."""
    import numpy as np
    from mxnet_tpu.test_utils import counted
    rt = _linear_runtime()
    fn, args, pools = _linear_program(
        rt, kind, b, lambda shape, dtype: jax.ShapeDtypeStruct(
            tuple(shape), dtype, sharding=one_chip))
    k_pool, _v, kda_pool, conv_pool = pools
    assert kda_pool.shape == (2, 33, 64, 128, 128) and \
        kda_pool.dtype == jnp.float32
    assert conv_pool.shape == (2, 33, 576, 128) and \
        k_pool.shape == (1, _K_PAGES, _PAGE, 1024)
    compiled = None

    def lower():
        nonlocal compiled
        compiled = fn.lower(*args).compile()

    lowered = counted("kda.step.path", lower)
    what = f"linear {kind}-b{b}"
    own = {int(np.prod(p.shape)) for p in pools}
    weights = {tuple(p.shape) for p in args[0]}
    layer = int(np.prod(kda_pool.shape[1:]))
    text = compiled.as_text()
    for op, dtype, dims in _materialised(text):
        if op in ("parameter", "get-tuple-element", "bitcast") or \
                dims in weights:
            continue
        n = int(np.prod(dims))
        if n in own:
            assert op != "copy", f"{what}: copies a whole pool {dtype}{dims}"
            continue
        assert n < layer, \
            f"{what}: {op} writes {dtype}{list(dims)}, a layer of the " \
            f"state pool or more"
        if kind == "step":
            assert _K_ROW_PAGES * _PAGE not in dims and \
                dims[-3:-1] != (_K_ROW_PAGES, _PAGE), \
                f"{what}: {op} writes {dtype}{list(dims)}: a reserved " \
                f"context"
    stats = compiled.memory_analysis()
    pool_bytes = sum(int(np.prod(p.shape)) * p.dtype.itemsize for p in pools)
    assert stats.alias_size_in_bytes >= pool_bytes, what
    calls = [m.group(2) for m in _KDA_CALL.finditer(text)]
    if kind == "commit":
        assert not calls and not lowered, what
        return
    assert lowered == {f'{{kind="kernel",rows="{b}"}}': 2}, what
    assert len(calls) == 2, f"{what}: {len(calls)} recurrence kernels for " \
        f"2 KDA layers"
    for rest in calls:
        assert "/kda.recur/" in rest and "kda_step_slots" in rest, \
            f"{what}: the kernel left its scope: {rest[:300]}"
        assert "output_to_operand_aliasing={{0}: (4, {})}" in rest, what
    paged = _paged_calls(text)
    assert len(paged) == 1 and "/attn.gqa/" in paged[0][1], \
        f"{what}: {len(paged)} paged-attention kernels under attn.gqa for " \
        f"1 grouped-query layer"
    # the rows' vectors (three of 8192 a KDA layer, the tails' 147 KB a
    # row), weights streamed ahead of their use; ONE row's state of one
    # layer is 4.19 MB and a row's reserved context 10.5 MB
    assert stats.temp_size_in_bytes < (24 << 20) + b * (1 << 20), \
        f"{what}: {stats.temp_size_in_bytes / 1e6:.1f} MB of temporaries " \
        f"beside {pool_bytes / 1e9:.3f} GB of pools"


# ------------------------------------------ the EVA-attention byte-level block's
# the cell's geometry (evabyte_pp2.doc_bytes): 385 pages of 16 summary rows,
# 48 a row (12,288 bytes at 16 bytes a row), 9 state rows (8 slots + trash)
_E_PAGES, _E_ROW_PAGES, _E_SLOTS, _E_SEQ = 385, 48, 8, 2048


def _eva_runtime():
    """EvaByte's layer at every published width (hidden 4,096, 32 heads of
    128, SwiGLU 11,008, a window of 2,048 and chunks of 16, eight heads of
    320 logits), two layers."""
    import mxnet_tpu as mx
    from mxnet_tpu.serving.decode import DecodeRuntime, EvaLM, PagedKVCache
    net = EvaLM(vocab_size=320, hidden_size=4096, num_layers=2,
                num_attention_heads=32, intermediate_size=11008,
                window_size=2048, chunk_size=16, num_pred_heads=8,
                max_length=_E_ROW_PAGES * _PAGE * 16)
    for p in net.collect_params().values():
        p._load_init(mx.nd.zeros(p.shape, dtype=p.dtype), None)
    cache = PagedKVCache(layout=net.cache_layout(), page_size=_PAGE,
                         num_pages=2, max_pages_per_seq=_E_ROW_PAGES,
                         max_slots=1)
    return DecodeRuntime(net, cache=cache, batch_buckets=(1,),
                         seq_buckets=(_E_SEQ,), warm=False)


def _eva_program(rt, kind, b, sds):
    """``(jitted program, its arguments with the pools last, the pools)``;
    the pools at the cell's size."""
    i32, u32, f32 = "int32", "uint32", "float32"
    blk, n_paged = rt.block, len(rt.cache.pool_layout)
    pools = tuple(
        sds(p.shape[:1] + ((_E_PAGES,) if j < n_paged else (_E_SLOTS + 1,))
            + p.shape[2:], p.dtype) for j, p in enumerate(rt.cache.pools))
    params = [sds(p.shape, p.dtype) for p in rt._params]
    if kind == "prefill":
        fn = jax.jit(lambda leaves, tok, ln: blk.prefill_math(
            blk._params_dict(leaves), tok, ln))
        return fn, (params, sds((b, _E_SEQ), i32), sds((b,), i32)), pools
    rows = (sds((b, _E_ROW_PAGES + 1), i32), sds((b, 2), u32),
            sds((b,), i32), sds((b,), f32))     # tables, keys, steps, temps
    if kind == "step":
        return rt._build_step(), \
            (params, sds((b,), i32), sds((b,), i32)) + rows + pools, pools
    state = tuple(sds(shape, dtype)
                  for shape, dtype in blk.prefill_state(b, _E_SEQ))
    return rt._build_commit(), \
        (params, state, sds((b, blk.vocab_size), f32), sds((b,), i32)) \
        + rows + pools, pools


def test_eva_prefill_holds_no_heads_by_s_by_s_array(one_chip):
    """A prompt of one window, 2,048 bytes: the float32 scores of ONE layer
    as a ``(heads, S, S)`` array would be 32 x 2,048^2 x 4 B = 537 MB.  The
    program goes by blocks of 512 queries over the window's keys and the
    chunks' summaries (32 x 512 x (2,048 + 128) float32, 143 MB) and the
    SwiGLU by windows of rows: no array is half of it, and all its
    temporaries together are less than it."""
    import numpy as np
    rt = _eva_runtime()
    assert rt.cache.context_length == 12288 and rt.cache.page_tokens == 256
    fn, args, _pools = _eva_program(
        rt, "prefill", 1, lambda shape, dtype: jax.ShapeDtypeStruct(
            tuple(shape), dtype, sharding=one_chip))
    compiled = fn.lower(*args).compile()
    heads, S = 32, _E_SEQ
    weights = {tuple(p.shape) for p in args[0]}
    for op, dtype, dims in _materialised(compiled.as_text()):
        if dims in weights:
            continue
        assert int(np.prod(dims)) <= heads * S * S // 2, \
            f"eva prefill: {op} writes {dtype}{list(dims)}"
        assert len(dims) < 3 or sum(d == S for d in dims) < 2, \
            f"eva prefill: {op} writes {dtype}{list(dims)}: S x S"
    assert compiled.memory_analysis().temp_size_in_bytes < \
        heads * S * S * 4


@pytest.mark.parametrize("kind,b", [("step", 1), ("step", 8), ("commit", 1)])
def test_eva_programs_touch_only_their_rings_and_pages(one_chip, kind, b):
    """A ring a slot (9 x 33.5 MB a layer) and summary pages in one donated
    tuple.  No step or commit program copies a pool, and each gives every
    pool back in the buffer it came in.  A step's attention is ONE kernel a
    layer (``eva_attention`` under the scope ``attn.eva``) that is handed
    the four WHOLE pools and fetches what is live: the program holds no
    ring (``b x 2,048`` entries of 32 x 128), in any dtype, and no gathered
    summaries (``b x 768`` rows of 4,096 were 6.3 MB a row and pool): its
    largest array is a streamed weight."""
    import numpy as np
    rt = _eva_runtime()
    fn, args, pools = _eva_program(
        rt, kind, b, lambda shape, dtype: jax.ShapeDtypeStruct(
            tuple(shape), dtype, sharding=one_chip))
    kbar, vbar, ring_k, ring_v = pools
    assert kbar.shape == vbar.shape == (2, _E_PAGES, _PAGE, 32, 128)
    assert ring_k.shape == ring_v.shape == (2, _E_SLOTS + 1, 2048, 32, 128)
    compiled = fn.lower(*args).compile()
    what = f"eva {kind}-b{b}"
    own = {int(np.prod(p.shape)) for p in pools}
    weights = {tuple(p.shape) for p in args[0]}
    for op, dtype, dims in _materialised(compiled.as_text()):
        if op in ("parameter", "get-tuple-element", "bitcast") or \
                dims in weights:
            continue
        n = int(np.prod(dims))
        if dtype == "bf16" and n in own:
            assert op != "copy", f"{what}: copies a whole pool {dtype}{dims}"
            continue
        if kind == "step":
            # no ring and no row's reserved summaries, in any dtype, and
            # nothing larger than a streamed weight
            assert not ({2048, 768} & set(dims) and dims[-2:] == (32, 128)), \
                f"{what}: {op} writes {dtype}{list(dims)}: a ring or a " \
                f"row's summaries"
            assert n <= 4096 * 11008, \
                f"{what}: {op} writes {dtype}{list(dims)}"
    stats = compiled.memory_analysis()
    pool_bytes = sum(int(np.prod(p.shape)) * p.dtype.itemsize for p in pools)
    assert stats.alias_size_in_bytes >= pool_bytes, what
    if kind == "commit":
        assert stats.temp_size_in_bytes < (8 << 20), what
        return
    # the kernel once a layer, under the scope the benchmark reads, each
    # handed the four pools whole
    calls = re.findall(r"custom_call_target=\"tpu_custom_call\"(.*)$",
                       compiled.as_text(), re.M)
    assert len(calls) == rt.block.num_layers and all(
        "/attn.eva/" in c and "eva_attention" in c for c in calls), \
        [c[:300] for c in calls]
    # the rows' vectors and weights streamed ahead of their use: 2.9 MB at
    # b = 1, 2.4 at b = 8 (sandbox compiles, PR 44).  With the summaries
    # gathered it was 3.0 and 55.6 (PR 43); with the rings gathered side by
    # side and handed to the MXU, 841 MB at b = 8
    assert stats.temp_size_in_bytes < (8 << 20) + b * (1 << 20), \
        f"{what}: {stats.temp_size_in_bytes / 1e6:.1f} MB of temporaries " \
        f"beside {pool_bytes / 1e9:.3f} GB of pools"


def test_eva_step_counts_the_kernel_once_a_layer(one_chip):
    """``decode.attn.eva.lowered``: lowering the 8-row step for the
    described chip counts ``kind="kernel"`` once a layer and ``plain``
    never; lowering the same program for the CPU, the other way round.
    Nothing but the platform differs between the two."""
    from mxnet_tpu.test_utils import counted
    rt = _eva_runtime()
    for sharding, kind in ((one_chip, "kernel"), (None, "plain")):
        fn, args, _pools = _eva_program(
            rt, "step", 8, lambda shape, dtype: jax.ShapeDtypeStruct(
                tuple(shape), dtype, sharding=sharding))
        assert counted("decode.attn.eva.lowered",
                       lambda: fn.lower(*args)) == \
            {f'{{kind="{kind}",rows="8"}}': rt.block.num_layers}


@pytest.mark.parametrize("block", ["base", "latent", "hybrid", "window",
                                   "linear"])
def test_other_blocks_programs_name_no_eva_kernel(one_chip, block):
    """The EVA kernel and its door are one block's: the step and the commit
    program of each of the five other block kinds lower, for the described
    chip, to text that names no ``eva_attention``, and count no
    ``decode.attn.eva.lowered``.  (That their text is the text they
    lowered to before the kernel existed no test can hold: ``PERF.md``
    section 6, PR 44, has the comparison with the parent's tree.)"""
    from mxnet_tpu.test_utils import counted
    rt, program, b = {
        "base": (lambda: _gpt2_medium_runtime(2, "float32"), _pool_program,
                 8),
        "latent": (_latent_runtime, _latent_pool_program, 32),
        "hybrid": (_hybrid_runtime, _hybrid_program, 32),
        "window": (_window_runtime, _window_program, 32),
        "linear": (_linear_runtime, _linear_program, 1)}[block]
    rt = rt()
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(tuple(shape), dtype,
                                                    sharding=one_chip)
    texts = []

    def lower():
        for kind, rows in (("step", b), ("commit", 1)):
            fn, args, *_pools = program(rt, kind, rows, sds)
            texts.append(fn.lower(*args).as_text())

    assert counted("decode.attn.eva.lowered", lower) == {}
    for text in texts:
        assert "eva_attention" not in text


# ------------------------------- the latent block's prefill, by blocks (PR 45)
_X_SEQ = 2048


@functools.lru_cache(maxsize=1)
def _xing4_prefill_block(layers=2):
    """A declared (no array) ``LatentMoELM`` at every attention width of
    ``xing4_29b_ep8`` (hidden 3,584, 32 heads of 128 + 64 and 128, ranks 768
    and 512, YaRN over 4,096) with a plain residual path and a narrow dense
    FFN in every layer: the prompt's attention is what is asked about, and
    four float32 streams of a 2,048-token prompt (117 MB an array) or its
    routed rows would hide it."""
    from mxnet_tpu.serving.decode import LatentMoELM
    return LatentMoELM(
        vocab_size=512, hidden_size=3584, num_layers=layers, num_heads=32,
        q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, intermediate_size=256,
        first_k_dense_replace=layers,
        rope_scaling={"factor": 64, "original_max_position_embeddings": 4096,
                      "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                      "mscale_all_dim": 1}, max_length=_X_SEQ + 256)


def _latent_prefill(net, s, sharding):
    """``(jitted prefill, its arguments)`` of one prompt of ``s``
    positions."""
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(tuple(shape), dtype,
                                                    sharding=sharding)
    # a plain residual path: every parameter is served as it is registered
    params = [sds(net._reg_params[n].shape, net._reg_params[n].dtype)
              for n in net._param_order]
    fn = jax.jit(lambda leaves, tok, ln: net.prefill_math(
        net._params_dict(leaves), tok, ln))
    return fn, (params, sds((1, s), "int32"), sds((1,), "int32"))


def test_latent_prefill_holds_no_heads_by_s_by_s_array(one_chip):
    """A prompt of 2,048 at ``xing4``'s attention widths: the float32 scores
    of ONE layer as a ``(heads, S, S)`` array are 32 x 2,048^2 x 4 B = 537
    MB, and the definition's chain passes such an array through device
    memory some five times a layer (3.2 ms; my chip run, PR 45).  Lowered for the chip the attention
    is one ``mla_prefill_attention`` call a layer under ``mla.attend``: no
    array has two axes of ``S``, the largest is a layer's per-head keys and
    values (``(S, 32 x 256)`` bfloat16, 34 MB), and all the program's
    temporaries together are under an eighth of that one array of scores."""
    import numpy as np
    net = _xing4_prefill_block()
    fn, args = _latent_prefill(net, _X_SEQ, one_chip)
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    heads, S = net.num_heads, _X_SEQ
    calls = re.findall(r"custom_call_target=\"tpu_custom_call\"(.*)$", text,
                       re.M)
    assert len(calls) == net.num_layers and all(
        "/mla.attend/" in c and "mla_prefill_attention" in c for c in calls)
    weights = {tuple(p.shape) for p in args[0]}
    for op, dtype, dims in _materialised(text):
        if dims in weights:
            continue
        assert int(np.prod(dims)) <= S * heads * 256, \
            f"latent prefill: {op} writes {dtype}{list(dims)}"
        # 32 heads' rotated queries side by side are 2,048 lanes wide, an
        # ``(S, S)`` that is no score: 8 MB, one sixty-fourth of the scores
        assert sum(d == S for d in dims) < 2 or dims == (1, S, heads * 64), \
            f"latent prefill: {op} writes {dtype}{list(dims)}: S x S"
    assert compiled.memory_analysis().temp_size_in_bytes < \
        heads * S * S * 4 // 8


@pytest.mark.parametrize("s", [256, 2048])
def test_latent_prefill_counts_the_kernel_once_a_layer(one_chip, s):
    """``decode.mla.prefill.lowered``: lowering the prefill for the
    described chip counts ``kind="kernel"`` once a layer and ``plain``
    never; lowering the same program for the CPU, the other way round.
    Nothing but the platform differs between the two."""
    from mxnet_tpu.test_utils import counted
    net = _xing4_prefill_block()
    for sharding, kind in ((one_chip, "kernel"), (None, "plain")):
        fn, args = _latent_prefill(net, s, sharding)
        assert counted("decode.mla.prefill.lowered",
                       lambda: fn.lower(*args)) == \
            {f'{{kind="{kind}",tokens="{s}"}}': net.num_layers}


@pytest.mark.parametrize("heads,s", [(32, 512), (32, 2048), (64, 256),
                                     (64, 1536)])
def test_mla_prefill_kernel_compiles_for_v5e(one_chip, heads, s):
    """The kernel alone at the two cells' head counts and their shortest and
    longest buckets, in the blocks the program picks: Mosaic takes the
    unaligned half-tile slices of the rotated queries and the scratch fits
    the core's memory."""
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                              sharding=one_chip)
    fn = jax.jit(functools.partial(pallas_kernels.mla_prefill_attention,
                                   scale=0.1))
    compiled = fn.lower(sds(1, s, heads, 128), sds(1, s, heads, 64),
                        sds(1, s, heads, 256), sds(1, s, 64)).compile()
    assert "mla_prefill_attention" in compiled.as_text()


@pytest.mark.parametrize("block", ["base", "hybrid", "window", "linear",
                                   "eva", "latent-step", "bert"])
def test_other_blocks_programs_name_no_mla_prefill_kernel(one_chip, block):
    """The prefill kernel and its door are one block's one program's: the
    step, the commit and (where the helper builds it) the prefill program of
    each of the five other block kinds, the latent block's own step and
    commit, and the BERT training step (one layer of it) lower, for the
    described chip, to text that names no ``mla_prefill_attention``, and
    count no ``decode.mla.prefill.lowered``."""
    from mxnet_tpu.test_utils import counted
    texts = []
    if block == "bert":
        from test_chip_compile_bert_step import _bert_base_step
        lower = lambda: texts.append(_bert_base_step(one_chip, 1).as_text())
    else:
        rt, program, b, kinds = {
            "base": (lambda: _gpt2_medium_runtime(2, "float32"),
                     _pool_program, 8, ("step", "commit")),
            "hybrid": (_hybrid_runtime, _hybrid_program, 32,
                       ("step", "commit", "prefill")),
            "window": (_window_runtime, _window_program, 32,
                       ("step", "commit", "prefill")),
            "linear": (_linear_runtime, _linear_program, 1,
                       ("step", "commit", "prefill")),
            "eva": (_eva_runtime, _eva_program, 8,
                    ("step", "commit", "prefill")),
            "latent-step": (_latent_runtime, _latent_pool_program, 32,
                            ("step", "commit"))}[block]
        rt = rt()
        sds = lambda shape, dtype: jax.ShapeDtypeStruct(tuple(shape), dtype,
                                                        sharding=one_chip)

        def lower():
            for kind in kinds:
                fn, args, *_pools = program(rt, kind,
                                            b if kind == "step" else 1, sds)
                texts.append(fn.lower(*args).as_text())

    assert counted("decode.mla.prefill.lowered", lower) == {}
    assert texts
    for text in texts:
        assert "mla_prefill_attention" not in text
