"""Plain reference of the family ``smallcnn``: the program's debug CNN (two
stride-2 3x3 conv + BN + ReLU of widths 16 and 32, mean pool, dense) — the
tiny stand-in the CPU rehearsal of the whole command checks against, where
a full-width ResNet would take minutes to compile. No cell on the chip uses
it. Its inputs are CIFAR-shaped images, so the image pipeline and the loss
are the ``resnet`` family's; the fp8 control rounds where that family's
does: both convolutions' and the head's inputs and weights.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp

from perfbench.references.resnet import (  # noqa: F401  (the interface)
    _bn, _conv, _dense, _stats, augment, eval_example_loss,
    example_loss, prepare)

WIDTHS = (16, 32)


def forward(params, model_state, inputs, arch: Mapping[str, Any],
            quantize: Optional[str] = None):
    """``model_state``: the BatchNorm running statistics; None is the
    training mode."""
    with jax.default_matmul_precision("highest"):
        x = inputs.astype(jnp.float32)
        for i in range(2):
            x = _conv(x, params[f"Conv_{i}"]["kernel"], 2, quantize)
            x = jnp.maximum(_bn(x, params[f"BatchNorm_{i}"],
                                _stats(model_state, f"BatchNorm_{i}")), 0.0)
        x = jnp.mean(x, axis=(1, 2))
        return _dense(x, params["Dense_0"], quantize)


def fwd_flops_per_example(config: Mapping[str, Any]) -> float:
    """2 x MACs of the two convolutions (all 9 taps at every output
    position) and the head."""
    h, c_in, macs = int(config["image_size"]), 3, 0.0
    for width in WIDTHS:
        h //= 2
        macs += 9.0 * h * h * c_in * width
        c_in = width
    return 2.0 * (macs + c_in * int(config["num_classes"]))
