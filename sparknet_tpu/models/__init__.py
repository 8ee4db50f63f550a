"""Model zoo: the reference's prototxt model family, built with the DSL.

Each builder returns a ``NetParameter`` Message ready for ``Network``/
``TPUNet``; ``*_solver()`` return the matching ``SolverConfig`` recipes
(ref: caffe/models/ + caffe/examples/).
"""

# Published input crop per benchmarkable zoo family — the single source
# for bench.py / tools/int8_bench.py / tools/scaling_bench.py (the three
# copies of this literal diverged once: a family added to one raised
# KeyError in another).
BENCH_CROPS = {
    "alexnet": 227, "caffenet": 227, "googlenet": 224, "mobilenet": 224,
    "resnet50": 224, "vgg16": 224, "squeezenet": 227,
}

from sparknet_tpu.models.classifier import Classifier  # noqa: F401,E402
from sparknet_tpu.models.generate import generate_chars  # noqa: F401,E402
from sparknet_tpu.models.deploy import DeployNet  # noqa: F401
from sparknet_tpu.models.detector import Detector  # noqa: F401
from sparknet_tpu.models.zoo import (  # noqa: F401
    alexnet,
    alexnet_solver,
    caffenet,
    caffenet_solver,
    cifar10_full,
    cifar10_full_solver,
    cifar10_quick,
    cifar10_quick_solver,
    googlenet,
    googlenet_solver,
    lenet,
    lenet_solver,
    mobilenet,
    mobilenet_solver,
    mnist_autoencoder,
    mnist_autoencoder_solver,
    mnist_siamese,
    mnist_siamese_solver,
    joyai_flash,
    joyai_flash_solver,
    laguna,
    laguna_solver,
    olmoe,
    olmoe_solver,
    ouro,
    ouro_solver,
    phi4_flash,
    phi4_flash_lambda_init,
    phi4_flash_role,
    phi4_flash_solver,
    qwen3_next,
    qwen3_next_solver,
    resnet50,
    resnet50_solver,
    squeezenet,
    squeezenet_solver,
    charlm,
    charlm_solver,
    transformer,
    transformer_solver,
    vgg16,
    vgg16_solver,
)
