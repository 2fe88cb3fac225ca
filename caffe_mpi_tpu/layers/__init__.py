"""Layer registry — importing this package registers all built-in layers.

Reference: src/caffe/layer_factory.cpp (the CreatorRegistry that maps
LayerParameter.type strings to constructors, plus its engine-dispatch
special cases). Here registration is an import side effect of each layer
module's `@register` decorator — no REGISTER_LAYER_CLASS macros.
"""

from .base import LAYER_REGISTRY, Layer, ParamDecl, create_layer, register, registered_types
from . import activations  # noqa: F401
from . import composite  # noqa: F401
from . import extension  # noqa: F401
from . import hyper_connection  # noqa: F401
from . import data_layers  # noqa: F401
from . import dense  # noqa: F401
from . import detection  # noqa: F401
from . import losses  # noqa: F401
from . import norm  # noqa: F401
from . import sequence  # noqa: F401
from . import shape_ops  # noqa: F401
from . import vision  # noqa: F401
