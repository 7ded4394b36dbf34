"""Flax model zoo — ResNet/VGG/MobileNetV2/BiLSTM-attention/Transformer.

``create_model`` is the factory the trainer uses (name-keyed, like the
reference's model selection global at ``pytorch_collab.py:25,255``).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from mercury_tpu.models.lstm import AdditiveAttention, BiLSTMAttention  # noqa: F401
from mercury_tpu.models.mobilenet import MobileNetV2  # noqa: F401
from mercury_tpu.models.resnet import (  # noqa: F401
    BasicBlock,
    Bottleneck,
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
from mercury_tpu.models.decoder import (  # noqa: F401
    LM_WIDTHS,
    CausalDecoder,
)
from mercury_tpu.models.moe import MoEMLP  # noqa: F401
from mercury_tpu.models.simple import SmallCNN  # noqa: F401
from mercury_tpu.models.transformer import (  # noqa: F401
    TransformerBlock,
    TransformerClassifier,
)
from mercury_tpu.models.vgg import CFG as VGG_CFG  # noqa: F401
from mercury_tpu.models.vgg import VGG, make_vgg  # noqa: F401

_RESNETS = {
    "resnet18": ResNet18,
    "resnet34": ResNet34,
    "resnet50": ResNet50,
    "resnet101": ResNet101,
    "resnet152": ResNet152,
}

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.float16}


def create_model(
    name: str,
    num_classes: int = 10,
    compute_dtype: str = "bfloat16",
    param_dtype: str = "float32",
    bn_axis_name: Optional[str] = None,
    **kwargs,
):
    """Build a model by name.

    Names: ``resnet18/34/50/101/152``, ``vgg11/13/16/19``, ``mobilenetv2``,
    ``bilstm_attention``, ``transformer``, ``vit``, and the causal decoders
    of ``LM_WIDTHS`` (``smallthinker-21b-a3b``, ``kanana-2-30b-a3b``: the
    name selects the published widths and with them the mixer and the
    routing rule, ``num_classes`` the vocabulary rows held, ``cut`` the
    layers, experts and heads held). ``bn_axis_name`` enables
    cross-replica synced BatchNorm over the given mesh axis (ignored by
    models without BN).
    """
    name = name.lower()
    cd, pd = _DTYPES[compute_dtype], _DTYPES[param_dtype]
    if name in _RESNETS:
        return _RESNETS[name](
            num_classes=num_classes, compute_dtype=cd, param_dtype=pd,
            bn_axis_name=bn_axis_name, **kwargs,
        )
    if name in VGG_CFG:
        return make_vgg(
            name, num_classes=num_classes, compute_dtype=cd, param_dtype=pd,
            bn_axis_name=bn_axis_name, **kwargs,
        )
    if name in ("mobilenetv2", "mobilenet_v2"):
        return MobileNetV2(
            num_classes=num_classes, compute_dtype=cd, param_dtype=pd,
            bn_axis_name=bn_axis_name, **kwargs,
        )
    if name == "smallcnn":
        return SmallCNN(num_classes=num_classes, compute_dtype=cd, param_dtype=pd,
                        bn_axis_name=bn_axis_name, **kwargs)
    if name in ("bilstm_attention", "mylstm", "lstm"):
        return BiLSTMAttention(num_classes=num_classes, compute_dtype=cd,
                               param_dtype=pd, **kwargs)
    if name in ("transformer", "vit"):
        if name == "vit":
            # Vision transformer for the CIFAR-shaped datasets: patchified
            # image input through the SAME TransformerClassifier stack, so
            # Megatron TP shardings, pipeline staging, and MoE blocks
            # apply to image training unchanged. max_len defaults to the
            # 32×32 token count for the chosen patch size — pass max_len
            # explicitly for other image sizes.
            kwargs.setdefault("patch_size", 4)
            kwargs.setdefault("num_layers", 4)
            kwargs.setdefault("max_len", (32 // kwargs["patch_size"]) ** 2)
        return TransformerClassifier(num_classes=num_classes, compute_dtype=cd,
                                     param_dtype=pd, **kwargs)
    if name in LM_WIDTHS:
        return CausalDecoder(num_classes=num_classes, widths=LM_WIDTHS[name],
                             compute_dtype=cd, param_dtype=pd, **kwargs)
    raise ValueError(f"unknown model {name!r}")
