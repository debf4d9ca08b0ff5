"""Model zoo: ResNet family (CIFAR variant) and Transformer encoder.

Flax re-designs of the reference's model zoo (resnet.py, transformer.py):
same architectures and hyperparameters, NHWC/TPU-native layouts, proper
train/eval semantics (running BN statistics, mixup gated on `train`).
"""

from faster_distributed_training_tpu.models.resnet import (  # noqa: F401
    ResNet, resnet18, resnet34, resnet50, resnet101, resnet152)
from faster_distributed_training_tpu.models.transformer import (  # noqa: F401
    Transformer)

_RESNETS = {
    "resnet18": resnet18, "resnet34": resnet34, "resnet50": resnet50,
    "resnet101": resnet101, "resnet152": resnet152,
}


def get_model(name: str, num_classes: int, **kw):
    """Factory matching the reference's get_model (resnet50_test.py:460-468)."""
    if name in _RESNETS:
        return _RESNETS[name](num_classes=num_classes, **kw)
    if name == "transformer":
        return Transformer(n_class=num_classes, **kw)
    if name == "decoder":
        # imported here: no other model's program sees the decoder's code
        from faster_distributed_training_tpu.models.decoder import Decoder
        return Decoder(**kw)
    raise ValueError(f"unknown model {name!r}; "
                     f"have {sorted(_RESNETS) + ['transformer', 'decoder']}")
