"""Operations a configuration's model REQUIRES, counted from its shapes.

``resnet_forward_flops`` counts the forward pass of the CIFAR-stem ResNet
for one image as 2 x multiply-accumulates of every convolution and of the
dense head (BatchNorm, ReLU and the pooling mean are left out, as is the
convention for model FLOPs; XLA's own count of the plain reference's
forward, which includes them, is 1-2 % higher and the tier-1 test holds
the two together). A training step requires 3 x forward (forward, and a
backward of twice its cost); the scoring pass of importance sampling is
extra work the method chooses to do and does not count, as recomputation
does not. Each configuration file carries the constant
(``fwd_flops_per_example``); the test holds it to this function.
"""

from __future__ import annotations

from typing import Any, Mapping

TRAIN_FLOPS_PER_FORWARD = 3.0


def resnet_forward_flops(arch: Mapping[str, Any], image_size: int = 32,
                         num_classes: int = 10, width: int = 64,
                         skip_padding: bool = False) -> float:
    """``arch``: the config file's ``reference`` group (``stage_sizes``,
    ``block``). SAME padding: a conv at stride s over an HxH map writes
    (H/s)^2 positions, each counted with all 9 taps of a 3x3 kernel — the
    convention of the literature (0.56 GMAC for CIFAR ResNet-18) and what
    the MXU executes. ``skip_padding`` leaves out the taps that fall on
    the zero padding, which is how XLA's cost analysis counts (13 % fewer
    for ResNet-18 at 32x32, whose last stage is a 4x4 map); the tier-1
    test holds that variant to XLA's count within 2 %."""

    def taps3(h_in: int, stride: int) -> float:
        """3x3 taps per image row/column, summed over output positions."""
        if not skip_padding:
            return 3.0 * (h_in // stride)
        # stride 1 pads one each side; stride 2 over an even extent pads
        # one at the far side only.
        return 3.0 * h_in - 2 if stride == 1 else 3.0 * (h_in // 2) - 1

    macs = 0.0
    h = image_size
    macs += taps3(h, 1) ** 2 * 3 * width                   # stem 3x3
    c_in = width
    expansion = 1 if arch["block"] == "basic" else 4
    for stage, n_blocks in enumerate(arch["stage_sizes"]):
        f = width * 2 ** stage
        for b in range(n_blocks):
            stride = 2 if stage > 0 and b == 0 else 1
            h_out = h // stride
            c_out = f * expansion
            if arch["block"] == "basic":
                macs += taps3(h, stride) ** 2 * c_in * f     # 3x3, stride
                macs += taps3(h_out, 1) ** 2 * f * f         # 3x3
            else:
                macs += h * h * c_in * f                   # 1x1
                macs += taps3(h, stride) ** 2 * f * f        # 3x3, stride
                macs += h_out * h_out * f * c_out          # 1x1
            if stride != 1 or c_in != c_out:
                macs += h_out * h_out * c_in * c_out       # projection
            h, c_in = h_out, c_out
    macs += c_in * num_classes                             # dense head
    return 2.0 * macs


def train_flops_per_example(fwd_flops_per_example: float) -> float:
    return TRAIN_FLOPS_PER_FORWARD * fwd_flops_per_example


def step_program_flops(fwd_flops_per_example: float, fields) -> float:
    """What one worker's step program DOES, from the job's ``TrainConfig``
    fields: forward and backward over the batch, and under importance
    sampling one scoring forward over the candidate pool (every
    ``score_refresh_every``-th step). The numerator of
    ``step_roofline_share``. Counted from the shapes and not taken from
    XLA's cost analysis, which adds both branches of a ``cond`` (the
    pipelined step's priming branch, taken at step 0 only, would count a
    second scoring forward into every step)."""
    batch = float(fields["batch_size"])
    pool = 0.0
    if fields.get("use_importance_sampling", True):
        pool = (batch * float(fields["presample_batches"])
                / float(fields.get("score_refresh_every", 1)))
    return fwd_flops_per_example * (pool + TRAIN_FLOPS_PER_FORWARD * batch)
