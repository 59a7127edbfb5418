"""He et al.'s CIFAR ResNet family (arXiv 1512.03385, Sec. 4.2) as the
program trains it: a 3x3 stem, three stages of ``blocks_per_stage`` basic
blocks at widths (w, 2w, 4w), stride 2 entering stages 2 and 3, a 1x1
projection where the shape changes, GroupNorm with 8 groups, global
average pooling and a linear classifier.

A configuration names this family (``"family": "resnet_cifar"``); the
training driver takes the program's model settings from
``program_fields`` and the work counts from ``forward_flops`` and
``train_flops``. The plain model is ``reference/resnet_cifar.py``.
"""
from __future__ import annotations

from typing import Dict

from chipbench.work import conv_flops

# what the program's ResNet has, and nothing else
NORM = "group_norm_8"
SHORTCUT = "projection"


def program_fields(model: Dict) -> Dict:
    """``FLConfig`` fields that the model sets: the program's model
    settings and the data geometry they imply."""
    from repro.configs.paper_resnet_speech import ResNetConfig

    if model["norm"] != NORM or model["shortcut"] != SHORTCUT:
        raise ValueError(f"the program's ResNet has norm {NORM!r} and "
                         f"shortcut {SHORTCUT!r}; the configuration asks "
                         f"for {model['norm']!r} and {model['shortcut']!r}")
    return {"model": ResNetConfig(n_classes=model["n_classes"],
                                  in_channels=model["in_channels"],
                                  width=model["width"],
                                  blocks_per_stage=model["blocks_per_stage"],
                                  input_hw=model["input_hw"]),
            "n_classes": model["n_classes"], "input_hw": model["input_hw"]}


def stem_flops(model: Dict) -> int:
    return conv_flops(model["input_hw"], 3, 1, model["in_channels"],
                      model["width"])


def forward_flops(model: Dict) -> int:
    """Multiply-adds of every convolution (taps on ``SAME`` padding left
    out) and of the classifier, at 2 FLOPs each, per sample."""
    w, hw = model["width"], model["input_hw"]
    flops = stem_flops(model)
    cin = w
    for si, cout in enumerate((w, 2 * w, 4 * w)):
        for bi in range(model["blocks_per_stage"]):
            stride = 2 if (bi == 0 and si > 0) else 1
            flops += conv_flops(hw, 3, stride, cin, cout)
            if cin != cout or stride != 1:
                flops += conv_flops(hw, 1, stride, cin, cout)
            hw = -(-hw // stride)
            flops += conv_flops(hw, 3, 1, cout, cout)
            cin = cout
    return flops + 2 * cin * model["n_classes"]


def train_flops(model: Dict) -> int:
    """Forward and backward FLOPs of one training sample: the backward
    pass costs two forward passes (gradients of the inputs and of the
    weights) less the stem's input gradient, which nothing needs."""
    return 3 * forward_flops(model) - stem_flops(model)
