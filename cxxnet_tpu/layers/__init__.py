"""The layer zoo: every layer type of the reference, as pure JAX functions.

Importing this package populates the registry; use ``create_layer(name)``.
"""

from .base import (  # noqa: F401
    Layer,
    LayerParam,
    LossLayer,
    Params,
    Shape,
    create_layer,
    layer_types,
    register,
)
from . import (  # noqa: F401
    conv,
    elemwise,
    embed,
    gdn,
    linear,
    loss,
    mla,
    moe,
    sequence,
    ssm,
    structure,
)
from .pairtest import PairTestLayer  # noqa: F401
