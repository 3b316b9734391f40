"""Activation layers (reference ``python/mxnet/gluon/nn/activations.py``)."""
from __future__ import annotations

from ..block import HybridBlock

__all__ = ["Activation", "LeakyReLU", "PReLU", "ELU", "SELU", "Swish", "GELU"]


class Activation(HybridBlock):
    """Applies an activation by name: relu/sigmoid/tanh/softrelu/softsign
    (reference ``activations.py:30``, backed by the ``Activation`` op)."""

    def __init__(self, activation, **kwargs):
        self._act_type = activation
        super().__init__(**kwargs)

    def _alias(self):
        return self._act_type

    def hybrid_forward(self, F, x):
        return F.Activation(x, act_type=self._act_type, name="fwd")

    def __repr__(self):
        s = "{name}({_act_type})"
        return s.format(name=self.__class__.__name__, **self.__dict__)


class LeakyReLU(HybridBlock):
    """Leaky ReLU (reference ``activations.py:77``)."""

    def __init__(self, alpha, **kwargs):
        assert alpha >= 0, "Slope coefficient for LeakyReLU must be no less than 0."
        super().__init__(**kwargs)
        self._alpha = alpha

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="leaky", slope=self._alpha, name="fwd")

    def __repr__(self):
        s = "{name}({alpha})"
        return s.format(name=self.__class__.__name__, alpha=self._alpha)


class PReLU(HybridBlock):
    """Parametric leaky ReLU with learned slope (reference
    ``activations.py:115``)."""

    def __init__(self, alpha_initializer=None, **kwargs):
        super().__init__(**kwargs)
        from ... import initializer
        if alpha_initializer is None:
            alpha_initializer = initializer.Constant(0.25)
        with self.name_scope():
            self.alpha = self.params.get("alpha", shape=(1,),
                                         init=alpha_initializer)

    def hybrid_forward(self, F, x, alpha):
        return F.LeakyReLU(x, alpha, act_type="prelu", name="fwd")


class ELU(HybridBlock):
    """Exponential Linear Unit (reference ``activations.py:149``)."""

    def __init__(self, alpha=1.0, **kwargs):
        super().__init__(**kwargs)
        self._alpha = alpha

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="elu", slope=self._alpha)


class SELU(HybridBlock):
    """Scaled ELU (reference ``activations.py:177``)."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="selu", name="fwd")


class Swish(HybridBlock):
    """Swish: x * sigmoid(beta*x) (reference ``activations.py:199``)."""

    def __init__(self, beta=1.0, **kwargs):
        super().__init__(**kwargs)
        self._beta = beta

    def hybrid_forward(self, F, x):
        return x * F.sigmoid(self._beta * x, name="fwd")


class GELU(HybridBlock):
    """Gaussian Error Linear Unit — x * Φ(x), the exact erf form.  Not in
    the 1.5 reference layer set but required by the transformer/BERT model
    family (BASELINE config).  One op (``LeakyReLU(act_type="gelu")``), so
    that its differentiation rule is its own (``ops/elemwise.py::gelu``)."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="gelu", name="fwd")
