"""Plain reference of the family ``resnet``: the CIFAR-stem ResNet forward
pass, its image pipeline and its loss.

``jax.numpy`` and ``lax.conv_general_dilated`` in float32 under
``jax.default_matmul_precision("highest")``; no flax module and no import
from the program. It follows the published equations (He et al. 2015,
arXiv:1512.03385, in the CIFAR variant the program trains): 3x3 stem at
stride 1 with no max-pool, four stages of widths 64/128/256/512 at strides
1/2/2/2, BasicBlock (3x3-3x3) or Bottleneck (1x1-3x3-1x1, expansion 4), a
1x1 projection shortcut wherever the shape changes, SAME padding,
BatchNorm (eps 1e-5; running mean/var at inference, the batch's own biased
mean/var in training), ReLU, global mean pool, dense head. Convolutions
carry no bias.

It reads a parameter tree by the names flax gives the program's modules
(``Conv_i``/``BatchNorm_i`` inside ``BasicBlock_j``/``Bottleneck_j``,
``Dense_0``) — names and shapes only, never the modules.

The configuration's ``reference`` group (``arch``) gives ``stage_sizes``,
``block`` (``"basic"`` or ``"bottleneck"``), ``mean`` and ``std`` of the
pixels, and ``sampling.pad``, the zero padding of the random crop.

**Where the fp8 control rounds** (``quantize="fp8"``): every convolution's
and the head's inputs and weights (e4m3, one scale per tensor), the nearest
precision below the bfloat16 the configurations state; BatchNorm, ReLU, the
pool and the residual sums stay in float32; a gradient passes straight
through the rounding.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perfbench.reference import round_to

BN_EPS = 1e-5


def _conv(x, kernel, stride: int, quantize):
    return lax.conv_general_dilated(
        round_to(x, quantize),
        round_to(kernel.astype(jnp.float32), quantize),
        window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)


def _bn(x, params, stats):
    """``stats`` None: training mode, the batch's own biased mean/var."""
    if stats is None:
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    else:
        mean, var = stats["mean"], stats["var"]
    return (x - mean) * (params["scale"] * lax.rsqrt(var + BN_EPS)) \
        + params["bias"]


def _stats(batch_stats, name):
    return None if batch_stats is None else batch_stats[name]


def _dense(x, p, quantize):
    return jnp.dot(round_to(x, quantize),
                   round_to(p["kernel"].astype(jnp.float32), quantize),
                   precision=lax.Precision.HIGHEST) + p["bias"]


def _block(x, p, s, kind: str, stride: int, quantize):
    """One residual block; the shortcut is a projection iff the tree holds
    one more conv than the main path (which is iff the shape changes)."""
    n_main = 2 if kind == "basic" else 3
    strides = ([stride, 1] if kind == "basic" else [1, stride, 1])
    y = x
    for i in range(n_main):
        y = _conv(y, p[f"Conv_{i}"]["kernel"], strides[i], quantize)
        y = _bn(y, p[f"BatchNorm_{i}"], _stats(s, f"BatchNorm_{i}"))
        if i < n_main - 1:
            y = jnp.maximum(y, 0.0)
    if f"Conv_{n_main}" in p:
        x = _conv(x, p[f"Conv_{n_main}"]["kernel"], stride, quantize)
        x = _bn(x, p[f"BatchNorm_{n_main}"], _stats(s, f"BatchNorm_{n_main}"))
    return jnp.maximum(x + y, 0.0)


def resnet_forward(params: Mapping[str, Any], batch_stats: Mapping[str, Any],
                   x, stage_sizes: Sequence[int], block: str,
                   quantize: Optional[str] = None):
    """Logits ``[N, classes]`` (float32) for normalized images ``x``
    ``[N, H, W, 3]``. ``block`` is ``"basic"`` or ``"bottleneck"``;
    ``batch_stats`` None is the training mode."""
    prefix = {"basic": "BasicBlock", "bottleneck": "Bottleneck"}[block]
    with jax.default_matmul_precision("highest"):
        x = x.astype(jnp.float32)
        x = _conv(x, params["Conv_0"]["kernel"], 1, quantize)
        x = jnp.maximum(_bn(x, params["BatchNorm_0"],
                            _stats(batch_stats, "BatchNorm_0")), 0.0)
        j = 0
        for stage, n_blocks in enumerate(stage_sizes):
            for b in range(n_blocks):
                name = f"{prefix}_{j}"
                x = _block(x, params[name], _stats(batch_stats, name), block,
                           2 if stage > 0 and b == 0 else 1, quantize)
                j += 1
        x = jnp.mean(x, axis=(1, 2))
        return _dense(x, params["Dense_0"], quantize)


# ------------------------------------------------------------ the interface
def prepare(raw_rows, arch: Mapping[str, Any]):
    """uint8 NHWC -> float32, ToTensor + Normalize(mean, std)."""
    x = raw_rows.astype(jnp.float32) / 255.0
    return ((x - jnp.asarray(arch["mean"], jnp.float32))
            / jnp.asarray(arch["std"], jnp.float32))


def augment(key, inputs, arch: Mapping[str, Any]):
    """Random crop after zero padding by ``sampling.pad`` and random
    horizontal flip (p = 0.5), one draw of each per image: offsets from the
    first and flips from the second of a 3-way split of ``key``."""
    pad = int(arch["sampling"]["pad"])
    n, h, w, _ = inputs.shape
    k_crop, k_flip, _ = jax.random.split(key, 3)
    off = jax.random.randint(k_crop, (n, 2), 0, 2 * pad + 1)
    flip = jax.random.bernoulli(k_flip, shape=(n,))
    padded = jnp.pad(inputs, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    crop = jax.vmap(lambda img, o: lax.dynamic_slice(
        img, (o[0], o[1], 0), (h, w, img.shape[-1])))(padded, off)
    return jnp.where(flip[:, None, None, None], crop[:, :, ::-1, :], crop)


def forward(params, model_state, inputs, arch: Mapping[str, Any],
            quantize: Optional[str] = None):
    """``model_state``: the BatchNorm running statistics; None is the
    training mode (the batch's own)."""
    return resnet_forward(params, model_state, inputs,
                          tuple(arch["stage_sizes"]), arch["block"], quantize)


def example_loss(outputs, labels):
    """Softmax cross-entropy of ``[N, C]`` logits and ``[N]`` labels."""
    logp = jax.nn.log_softmax(outputs.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]


def eval_example_loss(outputs, labels):
    """The same of a block of held-out rows, in float64 numpy."""
    z = np.asarray(outputs, np.float64)
    z = z - z.max(axis=1, keepdims=True)
    logp = z - np.log(np.exp(z).sum(axis=1, keepdims=True))
    return -logp[np.arange(len(labels)), np.asarray(labels)]


def fwd_flops_per_example(config: Mapping[str, Any]) -> float:
    """The conventional count of the configuration's forward pass (2 x
    MACs of every conv and the head, padding taps included: 1.11 GFLOP for
    ResNet-18, 2.60 for ResNet-50 at 32x32)."""
    return resnet_forward_flops(config["reference"], config["image_size"],
                                config["num_classes"])


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
    test holds that variant to XLA's count within 2 %. BatchNorm, ReLU and
    the pooling mean are left out, as is the convention for model FLOPs."""

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
