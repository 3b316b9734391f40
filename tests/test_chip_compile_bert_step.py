"""Ask the chip's compiler before the chip: the BERT training step.

The training cell's ``SPMDTrainer`` step compiled for a *described* v5e
(``tests/test_chip_compile.py`` has the kernels and the decode blocks'
programs, and says how): its attention is the kernels and holds no T x T
float32 tensor, its matmuls read values and not recipes, and a dp x tp step
maps the kernels over the mesh.  A file of its own because a file is one
worker's under ``--dist loadfile`` and the twelve-layer step alone compiles
for four minutes.
"""
import os
import sys

import jax
import jax.numpy as jnp

from test_chip_compile import _materialised


def _bert_base_step(one_chip, num_layers=12):
    """The training cell's program compiled for the described chip: the
    ``SPMDTrainer`` step of BERT-base (``num_layers`` of its twelve) at
    (32, 512) with the mask passed and dropout 0.1."""
    import mxnet_tpu as mx
    from mxnet_tpu.models import get_bert_model
    from mxnet_tpu.parallel import (FunctionalOptimizer, SPMDTrainer,
                                    device_mesh)
    b, t, masked, vocab = 32, 512, 76, 30522
    net = get_bert_model("bert_base", vocab_size=vocab, max_length=t,
                         dropout=0.1, num_layers=num_layers)
    net.initialize()
    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()

    def loss_fn(out, label):
        _seq, _pooled, mlm, nsp = out
        return ce(mlm.reshape((-1, vocab)), mx.nd.slice_axis(
            label, axis=1, begin=0, end=masked).reshape((-1,))).mean() + \
            ce(nsp, mx.nd.slice_axis(label, axis=1, begin=masked,
                                     end=masked + 1).reshape((-1,))).mean()

    row = mx.nd.zeros((1, t), dtype="int32")
    net(row, row, mx.nd.ones((1, t)), mx.nd.zeros((1, masked), dtype="int32"))
    trainer = SPMDTrainer(
        net, loss_fn, FunctionalOptimizer("adam", 1e-4),
        device_mesh({"pp": 1, "dp": 1, "sp": 1, "tp": 1},
                    devices=jax.devices()[:1]), n_in=4)
    # the same step function, lowered for the described chip (the block's
    # own first forward, above, was lowered for the CPU: interpreted)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    state = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                                   trainer._state)
    data = (sds((b, t), jnp.int32), sds((b, t), jnp.int32),
            sds((b, t), jnp.float32), sds((b, masked), jnp.int32))
    return jax.jit(trainer._step_fn.__wrapped__, donate_argnums=(0,)) \
        .lower(state, data, sds((b, masked + 1), jnp.int32),
               sds((2,), jnp.uint32), sds((), jnp.uint32)).compile()


def test_bert_base_step_holds_no_float32_scores(one_chip):
    """The training cell's program.  Its attention is the kernels (forward
    and backward a layer), the only (.., 512, 512) values it writes to
    device memory are dropout's keep-masks, a byte an element, and its
    temporaries are the activations': 10.56 GB with the dense float32
    scores and probabilities (sandbox compile, PR 23), 8.11 GB with the
    kernels (PR 27), 9.34 GB since GELU's results (bfloat16, the width
    their matmuls multiply in: 12 x 100.7 MB) and the hidden-state
    dropouts' masks (25 x 12.6 MB) are values kept for the backward
    (PR 29).  The day a T x T float32 tensor comes back this names it."""
    t = 512
    compiled = _bert_base_step(one_chip)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 24
    for op, dtype, dims in _materialised(text):
        if dims[-2:] == (t, t):
            assert dtype in ("s8", "pred"), \
                f"the step writes {dtype}{list(dims)} ({op}): a T x T " \
                f"tensor wider than the keep-mask is back in device memory"
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps < 10.0e9, f"{temps / 1e9:.3f} GB of temporaries"


def test_bert_step_matmuls_read_their_operands(one_chip):
    """No matmul over the 16,384 tokens computes its operand from an
    expensive recipe: in two layers of the cell's step, no fusion that
    holds a ``convolution`` has an ``erf`` or a threefry round
    (``shift-right-logical``) among the instructions its operands are
    computed FROM, where XLA would run the recipe again for every output
    tile (``tools/fusion_audit.py``).  GELU's result and the dropouts'
    keep-masks are values (``ops/elemwise.py::as_value``).  An ``erf`` on
    a convolution's RESULT is not held against it: bias + GELU as
    ``ffn1``'s epilogue, and GELU's derivative on dH, run once an element.

    And the two feed-forward weight gradients, the same FLOPs and the same
    bytes of (W, m, v), cost alike by the compiler's own
    ``estimated_cycles``: 2,604,232 against 789,320 until PR 29, when
    ``ffn2``'s held GELU and a keep-mask as recipes."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        import fusion_audit
    finally:
        sys.path.pop(0)
    rows = fusion_audit.audit(_bert_base_step(one_chip, 2).as_text())
    over_tokens = [r for r in rows if any(
        "[32,512," in t for t in r["convolution_operands"])]
    assert len(over_tokens) >= 2 * 12       # six matmuls a layer, twice
    for r in over_tokens:
        assert not r["producer_recipes"], \
            f"{r['fusion']} ({r['result']}) computes a convolution " \
            f"operand from {r['producer_recipes']}: " \
            f"{r['estimated_cycles']:,} cycles"
    update = lambda shape: [r["estimated_cycles"] for r in rows
                            if r["result"].count(f"f32[{shape}]") == 3]
    ffn2, ffn1 = update("768,3072"), update("3072,768")
    assert len(ffn2) == len(ffn1) == 2
    assert max(ffn2) < 1.5 * min(ffn1), (ffn2, ffn1)


def test_dp_tp_step_maps_the_kernels_over_the_mesh(one_chip):
    """XLA partitions no Mosaic call, so a step traced for a dp x tp mesh
    maps the attention kernels over the shards itself (``shard_map`` in
    ``ops/__init__.py``, told the mesh by ``SPMDTrainer``): compiled for the
    four chips of the described host, two layers at BERT-base's widths with
    the mask and dropout run their kernels on (batch / dp, heads / tp)."""
    import numpy as np
    import mxnet_tpu as mx
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu.models import get_bert_model
    from mxnet_tpu.parallel import (FunctionalOptimizer, SPMDTrainer,
                                    device_mesh)
    from mxnet_tpu.parallel.sp_context import traced_mesh_scope
    b, t = 8, 128
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices).reshape(1, 2, 1, 2),
                ("pp", "dp", "sp", "tp"))

    class WithHead(mx.gluon.Block):
        def __init__(self, bert):
            super().__init__()
            self.bert = bert
            self.head = mx.gluon.nn.Dense(2)

        def forward(self, tokens, valid):
            return self.head(self.bert(tokens, None, valid)[1])

    model = WithHead(get_bert_model(
        "bert_base", vocab_size=1000, max_length=t, num_layers=2,
        dropout=0.1, use_decoder=False, use_classifier=False))
    model.initialize()
    model(mx.nd.zeros((2, t), dtype="int32"), mx.nd.ones((2, t)))
    trainer = SPMDTrainer(
        model, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
        FunctionalOptimizer("adam", 1e-4),
        device_mesh({"pp": 1, "dp": 2, "sp": 1, "tp": 2},
                    devices=jax.devices()[:4]), n_in=2)
    sds = lambda shape, dtype, spec: jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, spec))
    state = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype, a.sharding.spec), trainer._state)
    with traced_mesh_scope(mesh, "dp", "tp"):
        text = jax.jit(trainer._step_fn.__wrapped__).lower(
            state, (sds((b, t), jnp.int32, P("dp")),
                    sds((b, t), jnp.float32, P("dp"))),
            sds((b,), jnp.float32, P("dp")), sds((2,), jnp.uint32, P()),
            sds((), jnp.uint32, P())).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 4          # forward and backward of two layers
    # each on its shard: 4 of 8 rows, 6 of 12 heads (384 of 768 lanes)
    assert all("f32[4,128,384]" in ln for ln in calls)
