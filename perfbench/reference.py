"""Plain reference: the CIFAR-stem ResNet forward pass and the Mercury
training step around it (pool, scores, reweighted loss, Adam), nothing else.

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

The training step follows Mercury (``pytorch_collab.py:95-148`` of the
reference implementation): a shuffled wrapping stream hands out the next
pool of shard slots; the pool is normalized, cropped (zero pad 4) and
flipped; one training-mode forward scores it; ``p_i = (loss_i + alpha *
EMA) / sum`` is the sampling distribution; the drawn batch trains on
``mean(loss_i / (N p_i))``; Adam under a cosine schedule applies the
gradient. The random draws use ``jax.random`` with the key discipline the
program documents (an 8-way split of the step key; a 3-way split of the
augmentation key into crop offsets and flips), so that the pool can be
rebuilt row for row from a state the program hands over.

It reads a parameter tree by the names flax gives the program's modules
(``Conv_i``/``BatchNorm_i`` inside ``BasicBlock_j``/``Bottleneck_j``,
``Dense_0``) — names and shapes only, never the modules.

``quantize`` is the control of the ``correct`` check: the same forward with
every convolution's and the head's inputs and weights rounded to fp8
(e4m3, one scale per tensor), the nearest precision below the bfloat16 the
configurations state; a gradient passes straight through the rounding.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5
_E4M3_MAX = 448.0


def _round_to(x, quantize: Optional[str]):
    """``x`` as float32 after a round trip through the lower precision;
    a gradient passes straight through the rounding."""
    if quantize is None:
        return x
    if quantize != "fp8":
        raise ValueError(f"unknown precision {quantize!r}")
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
    r = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(r - x)


def _conv(x, kernel, stride: int, quantize):
    return lax.conv_general_dilated(
        _round_to(x, quantize),
        _round_to(kernel.astype(jnp.float32), quantize),
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
        head = params["Dense_0"]
        return jnp.dot(_round_to(x, quantize),
                       _round_to(head["kernel"].astype(jnp.float32), quantize),
                       precision=lax.Precision.HIGHEST) + head["bias"]


def smallcnn_forward(params, batch_stats, x, quantize: Optional[str] = None):
    """The program's debug CNN (two stride-2 3x3 conv + BN + ReLU, mean
    pool, dense) — the tiny stand-in the CPU rehearsal of the whole command
    checks against, where a full-width ResNet would take minutes to
    compile. No cell on the chip uses it."""
    with jax.default_matmul_precision("highest"):
        x = x.astype(jnp.float32)
        for i in range(2):
            x = _conv(x, params[f"Conv_{i}"]["kernel"], 2, quantize)
            x = jnp.maximum(_bn(x, params[f"BatchNorm_{i}"],
                                _stats(batch_stats, f"BatchNorm_{i}")), 0.0)
        x = jnp.mean(x, axis=(1, 2))
        head = params["Dense_0"]
        return jnp.dot(_round_to(x, quantize),
                       _round_to(head["kernel"].astype(jnp.float32), quantize),
                       precision=lax.Precision.HIGHEST) + head["bias"]


def forward(params, batch_stats, x, arch: Mapping[str, Any],
            quantize: Optional[str] = None):
    """Dispatch on the configuration file's ``reference.family``;
    ``batch_stats`` None is the training mode (batch statistics)."""
    if arch["family"] == "resnet":
        return resnet_forward(params, batch_stats, x,
                              tuple(arch["stage_sizes"]), arch["block"],
                              quantize)
    if arch["family"] == "smallcnn":
        return smallcnn_forward(params, batch_stats, x, quantize)
    raise ValueError(f"no plain reference for family {arch['family']!r}")


def normalize(images_u8, mean, std):
    """uint8 NHWC -> float32, ToTensor + Normalize(mean, std)."""
    x = images_u8.astype(jnp.float32) / 255.0
    return (x - jnp.asarray(mean, jnp.float32)) / jnp.asarray(std, jnp.float32)


def logits(params, batch_stats, images_u8, arch: Mapping[str, Any],
           quantize: Optional[str] = None, block_rows: int = 64):
    """Reference logits for raw uint8 images, in blocks of ``block_rows``
    rows so that float32 activations at the real widths stay small beside
    the program's own peak. ``arch`` is the configuration file's
    ``reference`` group."""
    import numpy as np

    fwd = jax.jit(lambda p, s, x: forward(
        p, s, normalize(x, arch["mean"], arch["std"]), arch, quantize))
    out = [np.asarray(fwd(params, batch_stats, images_u8[i:i + block_rows]))
           for i in range(0, images_u8.shape[0], block_rows)]
    return np.concatenate(out, axis=0)


def nll(logits_f32, labels):
    """Mean softmax cross-entropy of ``[N, C]`` logits, in float64 numpy."""
    import numpy as np

    z = np.asarray(logits_f32, np.float64)
    z = z - z.max(axis=1, keepdims=True)
    logp = z - np.log(np.exp(z).sum(axis=1, keepdims=True))
    return float(-logp[np.arange(len(labels)), np.asarray(labels)].mean())


# ------------------------------------------------------ the training step
def per_example_nll(logits_f32, labels):
    logp = jax.nn.log_softmax(logits_f32.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]


def make_loss_and_grad(arch: Mapping[str, Any],
                       quantize: Optional[str] = None):
    """``(params, images, labels, scaled_probs) -> (loss, grads)`` of the
    reweighted training loss ``mean(nll_i / (N p_i))``, training-mode
    BatchNorm over the batch, float32."""
    def loss(params, images, labels, scaled_probs):
        z = forward(params, None, images, arch, quantize)
        return jnp.mean(per_example_nll(z, labels) / scaled_probs)

    return jax.jit(jax.value_and_grad(loss))


def cosine_lr(count: int, peak: float, decay_steps: int) -> float:
    """Cosine annealing to zero over ``decay_steps`` updates (Loshchilov &
    Hutter 2017), read at the number of updates already applied."""
    import math

    frac = min(count, decay_steps) / decay_steps
    return peak * 0.5 * (1.0 + math.cos(math.pi * frac))


def adam_update(params, mu, nu, count: int, grads, lr: float,
                b1: float, b2: float, eps: float):
    """One Adam update (Kingma & Ba 2015, bias-corrected) on host trees of
    float32 arrays; ``count`` is the number of updates already applied.
    Returns ``(params, mu, nu)``."""
    import numpy as np

    t = count + 1
    leaves = jax.tree.leaves
    tree = jax.tree.structure(params)
    new_p, new_mu, new_nu = [], [], []
    for p, m, v, g in zip(leaves(params), leaves(mu), leaves(nu),
                          leaves(grads)):
        g = np.asarray(g, np.float32)
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * np.square(g)
        step = (m / (1.0 - b1 ** t)) / (np.sqrt(v / (1.0 - b2 ** t)) + eps)
        new_p.append(np.asarray(p - lr * step, np.float32))
        new_mu.append(m.astype(np.float32))
        new_nu.append(v.astype(np.float32))
    return (jax.tree.unflatten(tree, new_p), jax.tree.unflatten(tree, new_mu),
            jax.tree.unflatten(tree, new_nu))


def pool_slots(key, perm, cursor: int, pool_size: int):
    """The next ``pool_size`` positions of the shuffled wrapping stream:
    a stream that cannot serve them is reshuffled with ``key`` and read
    from its start."""
    import numpy as np

    perm = np.asarray(perm)
    if cursor + pool_size > perm.shape[0]:
        perm = np.asarray(jax.random.permutation(key, perm.shape[0]))
        cursor = 0
    return perm[cursor:cursor + pool_size]


def augment(key, images, pad: int):
    """Random crop after zero padding by ``pad`` and random horizontal flip
    (p = 0.5), one draw of each per image: offsets from the first and flips
    from the second of a 3-way split of ``key``."""
    n, h, w, _ = images.shape
    k_crop, k_flip, _ = jax.random.split(key, 3)
    off = jax.random.randint(k_crop, (n, 2), 0, 2 * pad + 1)
    flip = jax.random.bernoulli(k_flip, shape=(n,))
    padded = jnp.pad(images, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    crop = jax.vmap(lambda img, o: lax.dynamic_slice(
        img, (o[0], o[1], 0), (h, w, img.shape[-1])))(padded, off)
    return jnp.where(flip[:, None, None, None], crop[:, :, ::-1, :], crop)


def score_pool(params, step_key, perm, cursor: int, ema_value: float,
               ema_count: int, x_train_u8, y_train, shard_row,
               arch: Mapping[str, Any], pool_size: int,
               quantize: Optional[str] = None):
    """What one Mercury step makes of its pool, from the state before it:
    ``(images [N,H,W,C], labels [N], losses [N], scaled_probs [N] = N p)``.
    ``step_key`` is the state's key; of its 8-way split the first shuffles
    the stream and the second augments the pool."""
    import numpy as np

    sampling = arch["sampling"]
    keys = jax.random.split(step_key, 8)
    slots = pool_slots(keys[0], perm, cursor, pool_size)
    rows = np.asarray(shard_row)[slots]
    labels = jnp.asarray(np.asarray(y_train)[rows])

    @jax.jit
    def run(params, raw, labels, key):
        images = augment(key, normalize(raw, arch["mean"], arch["std"]),
                         int(sampling["pad"]))
        losses = per_example_nll(
            forward(params, None, images, arch, quantize), labels)
        return images, losses

    images, losses = run(params, jnp.asarray(np.asarray(x_train_u8)[rows]),
                         labels, keys[1])
    losses = np.asarray(losses, np.float64)
    return (np.asarray(images), np.asarray(labels), losses,
            scaled_probs(losses, ema_value, ema_count, sampling))


def scaled_probs(losses, ema_value: float, ema_count: int, sampling):
    """``N p_i`` over a scored pool: the EMA of the mean pool loss takes
    this pool in first (its first update sets it), then
    ``p_i = (loss_i + is_alpha * EMA) / sum``."""
    import numpy as np

    losses = np.asarray(losses, np.float64)
    mean = float(losses.mean())
    a = float(sampling["ema_alpha"])
    ema = mean if ema_count == 0 else a * ema_value + (1.0 - a) * mean
    scores = np.maximum(losses + float(sampling["is_alpha"]) * ema, 1e-12)
    return scores / scores.sum() * len(losses)
