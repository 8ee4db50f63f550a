"""Layer/op library: Caffe-semantic ops as pure JAX functions.

Importing this package registers every built-in layer type with the
registry (the analog of ``REGISTER_LAYER_CLASS``,
ref: caffe/src/caffe/layer_factory.cpp:41-214).
"""

from sparknet_tpu.ops.base import Layer, LayerOutput  # noqa: F401
from sparknet_tpu.ops.registry import create_layer, get_layer_class, register  # noqa: F401

# Side-effect imports: populate the registry.
from sparknet_tpu.ops import data_layers  # noqa: F401
from sparknet_tpu.ops import vision  # noqa: F401
from sparknet_tpu.ops import neuron  # noqa: F401
from sparknet_tpu.ops import blocks  # noqa: F401
from sparknet_tpu.ops import loss  # noqa: F401
from sparknet_tpu.ops import python_layer  # noqa: F401
from sparknet_tpu.ops import attention  # noqa: F401
from sparknet_tpu.ops import moe  # noqa: F401
from sparknet_tpu.ops import ssm  # noqa: F401
from sparknet_tpu.ops import linear_attention  # noqa: F401
