"""Symbolic model zoo.

TPU-native equivalent of the reference's example model catalog
(``example/image-classification/symbols/`` — alexnet.py, lenet.py, mlp.py,
vgg.py, resnet.py, resnext.py, inception-bn.py, inception-v3.py,
mobilenet.py, squeezenet.py) plus the Gluon model zoo's coverage
(python/mxnet/gluon/model_zoo/vision).  Every builder returns a
:class:`~mxnet_tpu.symbol.Symbol` ending in ``SoftmaxOutput`` named
``softmax`` so it drops straight into ``Module(symbol)`` with the default
label name, exactly like the reference training scripts.

``get_symbol(name, num_classes=..., **kwargs)`` dispatches by network name
the way ``example/image-classification/common/fit.py`` imports
``symbols/<network>.py`` and calls its ``get_symbol``.
"""
from . import mlp as _mlp
from . import lenet as _lenet
from . import alexnet as _alexnet
from . import vgg as _vgg
from . import resnet as _resnet
from . import resnext as _resnext
from . import inception_bn as _inception_bn
from . import inception_v3 as _inception_v3
from . import mobilenet as _mobilenet
from . import squeezenet as _squeezenet

from .mlp import get_symbol as mlp
from .lenet import get_symbol as lenet
from .alexnet import get_symbol as alexnet
from .vgg import get_symbol as vgg
from .resnet import get_symbol as resnet
from .resnext import get_symbol as resnext
from .inception_bn import get_symbol as inception_bn
from .inception_v3 import get_symbol as inception_v3
from .mobilenet import get_symbol as mobilenet
from .squeezenet import get_symbol as squeezenet
from .ssd import ssd_vgg16, ssd_toy
from . import ssd as _ssd
from .transformer import (transformer_lm, transformer_decode_step,
                          looped_transformer_lm)
from .generation import beam_search
from . import vit as _vit  # module ref BEFORE the function shadows the name
from .vit import vit
from . import transformer as _transformer
from . import densenet as _densenet

_REGISTRY = {
    "mlp": _mlp, "lenet": _lenet, "alexnet": _alexnet, "vgg": _vgg,
    "resnet": _resnet, "resnext": _resnext, "inception-bn": _inception_bn,
    "inception_bn": _inception_bn, "inception-v3": _inception_v3,
    "inception_v3": _inception_v3, "mobilenet": _mobilenet,
    "squeezenet": _squeezenet, "densenet": _densenet,
    "vit": _vit,
}


def get_symbol(network, **kwargs):
    """Build the named network, e.g. ``get_symbol('resnet', num_layers=50,
    num_classes=1000, image_shape='3,224,224')``."""
    if network not in _REGISTRY:
        raise ValueError(
            "unknown network %r; choose from %s" % (network, sorted(_REGISTRY)))
    return _REGISTRY[network].get_symbol(**kwargs)
