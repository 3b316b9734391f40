"""BERT model tests (BASELINE config 3: pretraining step, hybridize,
SPMD)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.models import get_bert_model, BERTClassifier


def _inputs(b=2, t=16, vocab=100, masked=3, seed=0):
    rng = np.random.RandomState(seed)
    tokens = mx.nd.array(rng.randint(0, vocab, (b, t)), dtype="int32")
    segments = mx.nd.array(rng.randint(0, 2, (b, t)), dtype="int32")
    mask = mx.nd.array((rng.rand(b, t) > 0.1).astype("float32"))
    positions = mx.nd.array(rng.randint(0, t, (b, masked)), dtype="int32")
    return tokens, segments, mask, positions


def test_bert_forward_shapes():
    net = get_bert_model("bert_tiny", vocab_size=100, max_length=32)
    net.initialize()
    tokens, segments, mask, positions = _inputs()
    seq, pooled, mlm, nsp = net(tokens, segments, mask, positions)
    assert seq.shape == (2, 16, 128)
    assert pooled.shape == (2, 128)
    assert mlm.shape == (2, 3, 100)
    assert nsp.shape == (2, 2)


def test_bert_hybridize_matches_eager():
    net = get_bert_model("bert_tiny", vocab_size=50, max_length=32,
                         dropout=0.0)
    net.initialize()
    tokens, segments, mask, positions = _inputs(vocab=50)
    seq_e, pooled_e, mlm_e, nsp_e = net(tokens, segments, mask, positions)
    net.hybridize()
    seq_h, pooled_h, mlm_h, nsp_h = net(tokens, segments, mask, positions)
    np.testing.assert_allclose(seq_e.asnumpy(), seq_h.asnumpy(), rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(mlm_e.asnumpy(), mlm_h.asnumpy(), rtol=2e-4,
                               atol=2e-5)


def test_bert_mask_zeroes_padded_attention():
    """Fully-masked key positions must not influence outputs."""
    net = get_bert_model("bert_tiny", vocab_size=50, max_length=32,
                         dropout=0.0)
    net.initialize()
    rng = np.random.RandomState(0)
    tok = rng.randint(0, 50, (1, 8))
    tokens = mx.nd.array(tok, dtype="int32")
    mask = mx.nd.array(np.array([[1, 1, 1, 1, 0, 0, 0, 0]], dtype="float32"))
    seq1 = net(tokens, None, mask)[0].asnumpy()
    tok2 = tok.copy()
    tok2[0, 4:] = rng.randint(0, 50, 4)  # change only padded tokens
    seq2 = net(mx.nd.array(tok2, dtype="int32"), None, mask)[0].asnumpy()
    np.testing.assert_allclose(seq1[:, :4], seq2[:, :4], rtol=1e-4,
                               atol=1e-5)


def test_bert_pretraining_step_converges():
    """MLM+NSP loss decreases over a few steps on a fixed batch."""
    vocab = 64
    net = get_bert_model("bert_tiny", vocab_size=vocab, max_length=32,
                         dropout=0.0)
    net.initialize()
    tokens, segments, mask, positions = _inputs(b=4, t=12, vocab=vocab,
                                                masked=4)
    rng = np.random.RandomState(1)
    mlm_labels = mx.nd.array(rng.randint(0, vocab, (4, 4)), dtype="float32")
    nsp_labels = mx.nd.array(rng.randint(0, 2, (4,)), dtype="float32")
    sce = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = mx.gluon.Trainer(net.collect_params(), "adam",
                               {"learning_rate": 1e-3})
    losses = []
    for _ in range(15):
        with mx.autograd.record():
            _, _, mlm, nsp = net(tokens, segments, mask, positions)
            l = sce(mlm.reshape((-1, vocab)),
                    mlm_labels.reshape((-1,))).mean() + \
                sce(nsp, nsp_labels).mean()
        l.backward()
        trainer.step(4)
        losses.append(float(l.asscalar()))
    assert losses[-1] < losses[0] * 0.8, (losses[0], losses[-1])


def test_bert_classifier():
    bert = get_bert_model("bert_tiny", vocab_size=50, max_length=32)
    net = BERTClassifier(bert, num_classes=3)
    net.initialize()
    tokens, segments, mask, _ = _inputs(vocab=50)
    out = net(tokens, segments, mask)
    assert out.shape == (2, 3)


def test_bert_spmd_train_step():
    """SPMD fused step over dp×tp mesh (the config-3 distributed path)."""
    from mxnet_tpu.parallel import SPMDTrainer, FunctionalOptimizer, make_mesh
    vocab = 32
    net = get_bert_model("bert_tiny", vocab_size=vocab, max_length=16,
                         dropout=0.0, use_decoder=False, use_classifier=False,
                         use_pooler=True)
    net.initialize()
    rng = np.random.RandomState(0)
    x = rng.randint(0, vocab, (8, 8)).astype("int32")
    y = rng.randint(0, 2, (8,)).astype("float32")

    class WithHead(mx.gluon.Block):
        def __init__(self, bert):
            super().__init__()
            self.bert = bert
            self.head = mx.gluon.nn.Dense(2)

        def forward(self, tokens):
            _, pooled = self.bert(tokens)
            return self.head(pooled)

    model = WithHead(net)
    model.initialize()
    model(mx.nd.array(x, dtype="int32"))  # materialize deferred params
    mesh = make_mesh(dp=4, tp=2)
    spmd = SPMDTrainer(model, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
                       FunctionalOptimizer("adam", 1e-3), mesh)
    l1 = float(spmd.step(x, y).asnumpy())
    l2 = float(spmd.step(x, y).asnumpy())
    assert np.isfinite(l1) and np.isfinite(l2)


def test_bert_sequence_parallel_matches_dp():
    """sp-sharded ring attention inside the fused step == plain dp run."""
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel import SPMDTrainer, FunctionalOptimizer, make_mesh
    vocab, T = 32, 16
    rng = np.random.RandomState(0)
    x = rng.randint(0, vocab, (8, T)).astype("int32")
    y = rng.randint(0, 2, (8,)).astype("float32")

    def build():
        mx.random.seed(7)
        np.random.seed(7)
        net = get_bert_model("bert_tiny", vocab_size=vocab, max_length=T,
                             dropout=0.0, use_decoder=False,
                             use_classifier=False)

        class WithHead(mx.gluon.Block):
            def __init__(self, bert):
                super().__init__()
                self.bert = bert
                self.head = mx.gluon.nn.Dense(2)

            def forward(self, tokens):
                _, pooled = self.bert(tokens)
                return self.head(pooled)

        model = WithHead(net)
        model.initialize()
        model(mx.nd.array(x, dtype="int32"))
        return model

    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    m1 = build()
    dp_tr = SPMDTrainer(m1, loss_fn, FunctionalOptimizer("sgd", 0.1),
                        make_mesh(dp=8))
    m2 = build()
    sp_tr = SPMDTrainer(m2, loss_fn, FunctionalOptimizer("sgd", 0.1),
                        make_mesh(dp=2, sp=4), sequence_parallel=True,
                        data_spec=P("dp", "sp"))
    l1 = [float(dp_tr.step(x, y).asnumpy()) for _ in range(3)]
    l2 = [float(sp_tr.step(x, y).asnumpy()) for _ in range(3)]
    np.testing.assert_allclose(l2, l1, rtol=2e-4, atol=2e-5)
    # Ulysses (all_to_all head-sharded) SP must match too — bert_tiny has 2
    # heads, so sp=2 divides them exactly
    m3 = build()
    ul_tr = SPMDTrainer(m3, loss_fn, FunctionalOptimizer("sgd", 0.1),
                        make_mesh(dp=4, sp=2), sequence_parallel=True,
                        sp_impl="ulysses", data_spec=P("dp", "sp"))
    l3 = [float(ul_tr.step(x, y).asnumpy()) for _ in range(3)]
    np.testing.assert_allclose(l3, l1, rtol=2e-4, atol=2e-5)


def test_bert_symbol_export_roundtrip(tmp_path):
    """BERT is shape-polymorphic enough to trace symbolically: hybridize →
    export (dual-file checkpoint) → load → bind → identical outputs
    (the deployment path reference users take through gluon export)."""
    mx.random.seed(0)
    net = get_bert_model("bert_tiny", vocab_size=50, max_length=32,
                         dropout=0.0)
    net.initialize()
    tokens, segments, mask, positions = _inputs(vocab=50)
    net.hybridize()
    ref = [o.asnumpy() for o in net(tokens, segments, mask, positions)]
    prefix = str(tmp_path / "bt")
    net.export(prefix)
    sym = mx.sym.load(prefix + "-symbol.json")
    loaded = mx.nd.load(prefix + "-0000.params")
    args = {k.split(":", 1)[1]: v for k, v in loaded.items()
            if k.startswith("arg:")}
    auxs = {k.split(":", 1)[1]: v for k, v in loaded.items()
            if k.startswith("aux:")}
    ins = [a for a in sym.list_arguments() if a not in args]
    feeds = dict(zip(ins, [tokens, segments, mask, positions]))
    ex = sym.simple_bind(ctx=mx.cpu(), grad_req="null",
                         **{k: v.shape for k, v in feeds.items()})
    ex.copy_params_from(args, auxs, allow_extra_params=True)
    outs = [o.asnumpy() for o in ex.forward(is_train=False, **feeds)]
    for a, b in zip(ref, outs):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def _encoder_cell(dense):
    """One post-LN layer with dropout 0.1 from a fixed seed; ``dense`` sends
    its attention down the dense tail (``use_flash_attention=False``)."""
    from mxnet_tpu.models import TransformerEncoderCell
    mx.random.seed(5)
    np.random.seed(5)
    cell = TransformerEncoderCell(128, 256, 2, dropout=0.1, prefix="cell_")
    cell.initialize()
    cell.attention._use_flash = not dense
    return cell


def test_blockwise_attention_keeps_the_key_chain():
    """A layer in training mode with a mask and dropout: the blockwise
    kernels against the dense tail under the same ``mx.random`` state.  The
    output and every parameter gradient agree to a float32 tolerance (so the
    keep-mask on the probabilities is the one ``Dropout`` draws on the dense
    (B * H, T, T) tensor, and the attention-output and feed-forward dropouts
    after it draw what they drew), and the NEXT stochastic call after the
    layer draws the same numbers on both."""
    from mxnet_tpu import autograd
    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.randn(2, 128, 128).astype("float32"))
    mask = mx.nd.array((np.arange(128)[None, :]
                        < np.array([[128], [77]])).astype("float32"))
    w = mx.nd.array(rng.randn(2, 128, 128).astype("float32"))
    runs = []
    for dense in (False, True):
        cell = _encoder_cell(dense)
        mx.random.seed(123)
        with autograd.record():
            out = cell(x, mask)
            loss = (out * w).sum()
        loss.backward()
        nxt = mx.nd.Dropout(mx.nd.ones((4, 64)), p=0.5,
                            mode="always").asnumpy()
        grads = {k: p.grad().asnumpy()
                 for k, p in cell.collect_params().items()}
        runs.append((out.asnumpy(), grads, nxt))
    (out_b, grads_b, next_b), (out_d, grads_d, next_d) = runs
    np.testing.assert_allclose(out_b, out_d, rtol=1e-4, atol=1e-5)
    assert grads_b.keys() == grads_d.keys() and len(grads_b) == 12
    for name in grads_d:
        scale = np.abs(grads_d[name]).max()
        np.testing.assert_allclose(grads_b[name], grads_d[name], rtol=1e-3,
                                   atol=1e-4 * scale, err_msg=name)
    np.testing.assert_array_equal(next_b, next_d)
    # and dropout did drop: the layer differs from its inference output
    assert np.abs(out_b - _encoder_cell(False)(x, mask).asnumpy()).max() > 0.1


def _attention_paths(fn):
    """``attention.path`` counts by kind made while ``fn`` runs."""
    from mxnet_tpu.test_utils import counted
    kinds = {}
    for label, n in counted("attention.path", fn).items():
        kind = label.split('kind="')[1].split('"')[0]
        kinds[kind] = kinds.get(kind, 0) + n
    return {k: n for k, n in kinds.items() if n}


def test_attention_path_counter_names_every_layers_path():
    """Twelve layers with a mask and dropout, in training mode, take the
    blockwise kernels twelve times and the dense tail never; what is left
    to the dense tail is a layer built with ``use_flash_attention=False``
    and a sequence-parallel scope with a mask or dropout (the ring takes
    neither)."""
    from mxnet_tpu import autograd
    from mxnet_tpu.models import MultiHeadAttention
    from mxnet_tpu.parallel import make_mesh, sequence_parallel_scope
    net = get_bert_model("bert_tiny", vocab_size=50, max_length=16,
                         num_layers=12, dropout=0.1)
    net.initialize()
    tokens, segments, mask, positions = _inputs(vocab=50)

    def train_forward():
        with autograd.train_mode():
            net(tokens, segments, mask, positions)
    assert _attention_paths(train_forward) == {"blockwise": 12}
    assert _attention_paths(
        lambda: net(tokens, segments, None, positions)) == {"blockwise": 12}

    x = mx.nd.ones((4, 16, 128))
    off = MultiHeadAttention(128, 2, use_flash_attention=False)
    off.initialize()
    assert _attention_paths(lambda: off(x)) == {"dense": 1}
    attn = MultiHeadAttention(128, 2)
    attn.initialize()
    attn(x)
    with sequence_parallel_scope(make_mesh(dp=2, sp=4)):
        with pytest.warns(UserWarning, match="dense T×T path"):
            assert _attention_paths(
                lambda: attn(x, mx.nd.ones((4, 16)))) == {"dense": 1}


def test_blockwise_attention_on_a_mesh_matches_one_device():
    """Traced for a dp x tp mesh the kernels are mapped over the shards
    (XLA partitions no Mosaic call): with a mask and dropout the step's
    losses are those of the one-device step, the keep-mask being the same
    bits wherever a shard lies."""
    import jax
    from mxnet_tpu.parallel import (SPMDTrainer, FunctionalOptimizer,
                                    device_mesh)
    vocab, T = 32, 16
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, vocab, (8, T)).astype("int32")
    valid = (np.arange(T)[None, :] < rng.randint(5, T + 1, (8, 1))) \
        .astype("float32")
    y = rng.randint(0, 2, (8,)).astype("float32")

    class WithHead(mx.gluon.Block):
        def __init__(self, bert):
            super().__init__()
            self.bert = bert
            self.head = mx.gluon.nn.Dense(2)

        def forward(self, tokens, valid):
            _, pooled = self.bert(tokens, None, valid)
            return self.head(pooled)

    def losses(dp, tp):
        mx.random.seed(7)
        np.random.seed(7)
        model = WithHead(get_bert_model(
            "bert_tiny", vocab_size=vocab, max_length=T, dropout=0.1,
            use_decoder=False, use_classifier=False))
        model.initialize()
        model(mx.nd.array(tokens, dtype="int32"), mx.nd.array(valid))
        tr = SPMDTrainer(model, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
                         FunctionalOptimizer("sgd", 0.1),
                         device_mesh({"pp": 1, "dp": dp, "sp": 1, "tp": tp},
                                     devices=jax.devices()[:dp * tp]),
                         n_in=2)
        mx.random.seed(11)
        return [float(tr.step((tokens, valid), y).asnumpy())
                for _ in range(3)]

    np.testing.assert_allclose(losses(dp=2, tp=2), losses(dp=1, tp=1),
                               rtol=2e-4, atol=2e-5)
