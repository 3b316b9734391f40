"""The curated op batch of the CPU↔TPU consistency suite (the reference's
``check_consistency`` role, ``python/mxnet/test_utils.py`` — same ops on
two backends, outputs must agree).  ``chip_smoke.py --ops`` runs it, with
the generated sweep, on the chip and on the CPU and compares; the batch is
defined HERE so both sides import one list.
"""
import numpy as np


def op_batch(mx, ctx):
    """name → NDArray output, deterministic inputs, every major op family.

    Exactness: run under ``default_matmul_precision('highest')`` so the
    MXU computes fp32 (bf16 rounding would need sloppy tolerances)."""
    rng = np.random.RandomState(42)

    def A(*shape, scale=1.0):
        return mx.nd.array(rng.randn(*shape).astype("float32") * scale,
                           ctx=ctx)

    x = A(2, 3, 8, 8)
    w = A(4, 3, 3, 3, scale=0.5)
    b = A(4)
    out = {}
    out["conv"] = mx.nd.Convolution(x, w, b, kernel=(3, 3), pad=(1, 1),
                                    num_filter=4)
    out["deconv"] = mx.nd.Deconvolution(x, A(3, 4, 3, 3, scale=0.5),
                                        kernel=(3, 3), stride=(2, 2),
                                        pad=(1, 1), num_filter=4)
    out["maxpool"] = mx.nd.Pooling(x, kernel=(2, 2), stride=(2, 2),
                                   pool_type="max")
    out["avgpool_full"] = mx.nd.Pooling(x, kernel=(3, 3), stride=(2, 2),
                                        pad=(1, 1), pool_type="avg",
                                        pooling_convention="full")
    gamma, beta = A(3, scale=0.3), A(3, scale=0.3)
    mean, var = A(3, scale=0.1), mx.nd.abs(A(3)) + 1.0
    out["bn_eval"] = mx.nd.BatchNorm(x, gamma, beta, mean, var,
                                     fix_gamma=False)
    out["fc"] = mx.nd.FullyConnected(A(4, 10), A(6, 10, scale=0.5), A(6),
                                     num_hidden=6)
    out["softmax"] = mx.nd.softmax(A(4, 7))
    out["log_softmax"] = mx.nd.log_softmax(A(4, 7))
    out["lrn"] = mx.nd.LRN(x, nsize=3, alpha=1e-3, beta=0.7)
    out["layernorm"] = mx.nd.LayerNorm(A(4, 9), A(9), A(9))
    out["dot_tn"] = mx.nd.dot(A(5, 4), A(5, 6), transpose_a=True)
    out["batch_dot"] = mx.nd.batch_dot(A(2, 3, 4), A(2, 4, 5))
    out["embedding"] = mx.nd.Embedding(
        mx.nd.array([1, 3, 0, 2], ctx=ctx), A(5, 6), input_dim=5,
        output_dim=6)
    out["take"] = mx.nd.take(A(6, 3), mx.nd.array([1, 4, 1], ctx=ctx))
    out["topk"] = mx.nd.topk(A(3, 9), k=3, ret_typ="value")
    out["sort"] = mx.nd.sort(A(3, 9), axis=1)
    out["sum_ax"] = mx.nd.sum(x, axis=(0, 2))
    out["max_ax"] = mx.nd.max(x, axis=1)
    out["norm2"] = mx.nd.norm(A(5, 5), ord=2)
    out["elem_chain"] = mx.nd.tanh(A(4, 4)) * mx.nd.sigmoid(A(4, 4)) + \
        mx.nd.relu(A(4, 4))
    out["erf_gamma"] = mx.nd.erf(A(3, 3)) + mx.nd.gammaln(
        mx.nd.abs(A(3, 3)) + 1.0)
    out["transpose"] = mx.nd.transpose(x, axes=(0, 2, 3, 1))
    out["slice"] = mx.nd.slice(x, begin=(0, 1, 2, 2), end=(2, 3, 6, 7))
    out["where"] = mx.nd.where(A(4, 4) > 0, A(4, 4), A(4, 4))
    out["leaky"] = mx.nd.LeakyReLU(A(4, 4), act_type="elu", slope=0.3)
    out["clip_sm"] = mx.nd.clip(mx.nd.smooth_l1(A(4, 4), scalar=1.5),
                                -0.8, 0.8)
    out["one_hot"] = mx.nd.one_hot(mx.nd.array([0, 2, 1], ctx=ctx), 4)
    out["grid_gen"] = mx.nd.GridGenerator(A(2, 6), transform_type="affine",
                                          target_shape=(4, 4))
    out["instance_norm"] = mx.nd.InstanceNorm(x, A(3), A(3), eps=1e-4)
    return out
