"""Operator implementations.  Importing this package registers all ops."""
from . import registry  # noqa: F401
from . import elemwise  # noqa: F401
from . import broadcast_reduce  # noqa: F401
from . import matrix  # noqa: F401
from . import nn  # noqa: F401
from . import random_ops  # noqa: F401
from . import init_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import linalg_ops  # noqa: F401
from . import image_ops  # noqa: F401
from . import contrib_ops  # noqa: F401
from . import quantization_ops  # noqa: F401
from . import extra_ops  # noqa: F401
from . import int8_ops  # noqa: F401
from . import detection_ops  # noqa: F401
from . import pallas_kernels  # noqa: F401

from .registry import get, list_ops, register, require  # noqa: F401

# flash attention as contrib ops (nd.contrib.flash_attention[_dropout]) —
# wrappers map string/kwarg attrs onto the custom_vjp function's
# positional-only signature.  ``mask`` (B, T), 1 = valid key, is an input
# that is there or not.
def _attend(q, k, v, mask, causal, scale, block_q, block_k, interpret,
            keep=None, rate=0.0):
    from ..base import parse_bool, parse_int
    from ..parallel.sp_context import traced_mesh
    none = (None, "None")
    attrs = (parse_bool(causal), None if scale in none else float(scale),
             None if block_q in none else parse_int(block_q),
             None if block_k in none else parse_int(block_k), interpret)
    scope = traced_mesh()
    if scope is None:
        return pallas_kernels.flash_attention(q, k, v, *attrs, mask, keep,
                                              rate)
    # traced for a mesh: XLA does not partition a Mosaic call, so map the
    # kernels over the shards: batch over dp, heads over tp where they
    # divide (an axis that does not divide leaves its dimension whole)
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mesh, dp_axis, tp_axis = scope
    b, h, t, _ = q.shape
    fits = lambda axis, n: axis if axis in mesh.shape and \
        n % mesh.shape[axis] == 0 else None
    dp, tp = fits(dp_axis, b), fits(tp_axis, h)
    # an operand that is not there is None on both sides of the map
    keep4 = None if keep is None else keep.reshape(b, h, t, t)
    specs = (P(dp, tp),) * 3 + (None if mask is None else P(dp),
                                None if keep is None else P(dp, tp))

    def local(q, k, v, mask, keep4):
        keep = None if keep4 is None else keep4.reshape(-1, t, t)
        return pallas_kernels.flash_attention(q, k, v, *attrs, mask, keep,
                                              rate)

    return shard_map(local, mesh=mesh, in_specs=specs, out_specs=P(dp, tp),
                     check_vma=False)(q, k, v, mask, keep4)


@register("_contrib_flash_attention", aliases=("flash_attention",))
def _flash_attention_op(q, k, v, mask=None, causal=False, scale=None,
                        block_q=None, block_k=None, interpret=None):
    return _attend(q, k, v, mask, causal, scale, block_q, block_k, interpret)


@random_ops._register_random("_contrib_flash_attention_dropout",
                             aliases=("flash_attention_dropout",))
def _flash_attention_dropout_op(key, q, k, v, mask=None, p=0.5, causal=False,
                                scale=None, block_q=None, block_k=None,
                                interpret=None, __training__=False):
    """``flash_attention`` with ``Dropout(p)`` on the probabilities.  Like
    ``Dropout`` it takes one key a call, whatever the mode, and in training
    keeps a probability where ``jax.random.bernoulli(key, 1 - p, (B * H, T,
    T))`` is true: the mask ``Dropout`` would draw on the dense (B * H, T,
    T) probabilities.  The kernels read it as int8, a tile a step."""
    from ..base import parse_bool, parse_float
    import jax
    rate = parse_float(p, 0.5)
    if not (parse_bool(__training__) and rate > 0.0):
        return _attend(q, k, v, mask, causal, scale, block_q, block_k,
                       interpret)
    b, h, t, _ = q.shape
    keep = jax.random.bernoulli(key, 1.0 - rate, (b * h, t, t))
    return _attend(q, k, v, mask, causal, scale, block_q, block_k, interpret,
                   keep.astype("int8"), rate)
