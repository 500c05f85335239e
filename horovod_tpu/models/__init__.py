"""Model zoo for horovod_tpu benchmarks and examples.

The reference ships no model library — its examples pull models from
``tf.keras.applications`` / ``torchvision`` (reference:
examples/tensorflow_synthetic_benchmark.py:10,44-45,
examples/pytorch_imagenet_resnet50.py). A TPU-native framework cannot lean on
those (torchvision has no TPU path; tf.keras is not the compute stack here),
so the models the reference's examples and headline benchmarks use are
implemented natively in flax: ResNet-50/101/152 and VGG-16 (the benchmark
models of reference README.md:45-50), the 2-layer MNIST convnet
(examples/tensorflow_mnist.py:30-63), word2vec skip-gram
(examples/tensorflow_word2vec.py), and a BERT-style transformer encoder (the
tensor-fusion stress config of BASELINE.json) with pluggable attention so the
long-context paths in :mod:`horovod_tpu.parallel` can drop in; and a causal
decoder built from per-layer specs (:mod:`horovod_tpu.models.decoder`: full
and window attention over grouped key-value heads, head counts by layer,
rotary positions, gated heads, normed queries and keys, gated short
convolutions, dense and sparse-expert SwiGLU MLPs under a softmax or a
sigmoid router, an untied or a tied head); and a
hybrid whose blocks are one mixer each (:mod:`horovod_tpu.models.hybrid`:
state-space mixers with a chunked scan, attention, latent sparse experts).

All models default to bfloat16 compute with float32 parameters — the MXU's
native mixed precision.
"""

from horovod_tpu.models.resnet import (  # noqa: F401
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
from horovod_tpu.models.vgg import VGG16  # noqa: F401
from horovod_tpu.models.inception import InceptionV3  # noqa: F401
from horovod_tpu.models.mnist import MnistConvNet, MnistMLP  # noqa: F401
from horovod_tpu.models.word2vec import Word2Vec  # noqa: F401
from horovod_tpu.models.transformer import (  # noqa: F401
    TransformerConfig,
    TransformerLM,
    BertBase,
)
from horovod_tpu.models.decoder import (  # noqa: F401
    Decoder,
    DecoderConfig,
    LayerSpec,
    RopeSpec,
    ShortConv,
    TiedHead,
)
from horovod_tpu.models.hybrid import HybridConfig, HybridLM  # noqa: F401
from horovod_tpu.models.sambay import SambaYConfig, SambaYLM  # noqa: F401

_REGISTRY = {
    "resnet18": ResNet18,
    "resnet34": ResNet34,
    "resnet50": ResNet50,
    "resnet101": ResNet101,
    "resnet152": ResNet152,
    "vgg16": VGG16,
    "inceptionv3": InceptionV3,
    "inception_v3": InceptionV3,
    "mnist_cnn": MnistConvNet,
    "mnist_mlp": MnistMLP,
}


def get_model(name: str, **kwargs):
    """Construct a vision model by name (benchmark scripts use this the way
    the reference uses ``getattr(applications, args.model)`` —
    examples/tensorflow_synthetic_benchmark.py:44-45)."""
    key = name.lower()
    if key not in _REGISTRY:
        raise ValueError(f"unknown model '{name}'; have {sorted(_REGISTRY)}")
    return _REGISTRY[key](**kwargs)
