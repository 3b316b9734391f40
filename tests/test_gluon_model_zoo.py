"""Model zoo tests (reference
``tests/python/unittest/test_gluon_model_zoo.py``): every registered model
constructs, initializes, and produces finite logits of the right shape.

Heavy models (vgg19, densenet201, resnet152...) are exercised at the
construct-only level to keep CI time bounded; one representative per family
runs a real forward, at the smallest image the family admits: "constructs,
initialises, finite logits of the right shape" does not depend on ImageNet's
224 x 224.  A family that ends in global pooling takes 32 x 32 (five
halvings to 1 x 1), and ``vgg11`` too (its first dense layer is then 512
wide, not 25,088 x 4,096 drawn in float64); the others end in a pool of a
fixed window, or in strides, and one pixel under the floor stated beside the
case leaves the first dense layer no input (a weight of shape ``(n, 0)``).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo import vision
from mxnet_tpu.gluon.model_zoo.vision import get_model

ALL_MODELS = [
    "resnet18_v1", "resnet34_v1", "resnet50_v1", "resnet101_v1",
    "resnet152_v1", "resnet18_v2", "resnet34_v2", "resnet50_v2",
    "resnet101_v2", "resnet152_v2",
    "vgg11", "vgg13", "vgg16", "vgg19",
    "vgg11_bn", "vgg13_bn", "vgg16_bn", "vgg19_bn",
    "alexnet", "densenet121", "densenet161", "densenet169", "densenet201",
    "squeezenet1.0", "squeezenet1.1", "inceptionv3",
    "mobilenet1.0", "mobilenet0.75", "mobilenet0.5", "mobilenet0.25",
    "mobilenetv2_1.0", "mobilenetv2_0.75", "mobilenetv2_0.5",
    "mobilenetv2_0.25",
]

FORWARD_MODELS = {
    "resnet18_v1": 32, "resnet18_v2": 32, "vgg11": 32,
    "alexnet": 63,              # floor: 1 x 1 out of the strides
    "densenet121": 221,         # floor: 7 x 7 into AvgPool2D(7)
    "squeezenet1.1": 209,       # floor: 13 x 13 into AvgPool2D(13)
    "mobilenet0.25": 32, "mobilenetv2_0.25": 32}


@pytest.mark.parametrize("name", ALL_MODELS)
def test_constructs(name):
    net = get_model(name, classes=7)
    assert net is not None


@pytest.mark.parametrize("name", FORWARD_MODELS)
def test_forward(name):
    net = get_model(name, classes=7)
    net.initialize()
    side = FORWARD_MODELS[name]
    x = mx.nd.random.uniform(shape=(2, 3, side, side))
    y = net(x)
    assert y.shape == (2, 7)
    assert np.isfinite(y.asnumpy()).all()


def test_inception_forward():
    net = get_model("inceptionv3", classes=5)
    net.initialize()
    # floor: 8 x 8 into AvgPool2D(8); at 298 the dense layer has no input
    x = mx.nd.random.uniform(shape=(1, 3, 299, 299))
    y = net(x)
    assert y.shape == (1, 5)
    assert np.isfinite(y.asnumpy()).all()


def test_hybridize_resnet():
    net = vision.resnet18_v1(classes=4)
    net.initialize()
    x = mx.nd.random.uniform(shape=(2, 3, 32, 32))
    # op by op first: it is the reference, and it finishes deferred
    # initialisation (hybridized cold, every child block compiles a program
    # of its own for that: ``ROADMAP.md`` D19; ``tests/test_gluon.py`` and
    # ``tests/test_gluon_deep.py`` hold that path)
    y0 = net(x)
    net.hybridize()
    y1 = net(x)
    y2 = net(x)
    np.testing.assert_allclose(y1.asnumpy(), y2.asnumpy(), rtol=1e-5)
    np.testing.assert_allclose(y1.asnumpy(), y0.asnumpy(), rtol=1e-4,
                               atol=1e-5)


def test_unknown_model_raises():
    with pytest.raises(ValueError):
        get_model("resnet1_v9")
