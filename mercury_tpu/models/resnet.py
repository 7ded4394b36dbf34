"""CIFAR-style ResNet family in Flax.

Capability parity with ``pytorch_model.py:14-113``: ``BasicBlock`` (3×3-3×3,
BN after each conv, 1×1-conv shortcut on stride/width change, ``:14-36``),
``Bottleneck`` (1×1-3×3-1×1, expansion 4, ``:39-64``), and the CIFAR stem
``ResNet`` (conv3×3(3→64)+BN — no 7×7/maxpool — 4 stages of widths
64/128/256/512 at strides 1/2/2/2, global average pool, linear head,
``:67-97``). Depth configs per ``ResNet18/34/50/101/152`` (``:100-113``).

TPU-first details the reference never faced:
- activations/matmuls in ``compute_dtype`` (bfloat16 by default) with fp32
  params — MXU-friendly;
- BatchNorm can be cross-replica: pass ``bn_axis_name`` to psum batch stats
  over the data mesh axis (the reference silently lets per-worker BN stats
  drift — SURVEY.md §7 "hard parts"); ``None`` reproduces local/drifting BN;
- a ``Bottleneck``'s closing 1×1 convolution + BatchNorm + shortcut add +
  ReLU is one unit (:func:`_closing_unit`) whose batch statistic comes from
  the convolution's INPUT moments when nothing differentiates the pass, so
  the block's output is written once, by the convolution (PERF.md §6, PR 30).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from mercury_tpu.ops import input_moments_pallas

ModuleDef = Any


class BasicBlock(nn.Module):
    """3×3-3×3 residual block (``pytorch_model.py:14-36``)."""

    filters: int
    strides: int
    conv: ModuleDef
    norm: ModuleDef
    expansion: int = 1

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), strides=(self.strides, self.strides))(x)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm()(y)
        if residual.shape != y.shape:  # 1×1-conv shortcut (:25-29)
            residual = self.conv(
                self.filters * self.expansion, (1, 1), strides=(self.strides, self.strides)
            )(residual)
            residual = self.norm()(residual)
        return nn.relu(residual + y)


# The collection a ``Bottleneck`` sows a 1 into for each closing unit it
# runs; mutable only where a caller counts them (train/stages.py).
MOMENT_UNITS = "bn_moment_units"


def _stat_from_output(y, axis_name):
    """Batch mean and variance of ``y`` over all but its last axis, as
    ``nn.BatchNorm`` takes them (flax's ``_compute_stats``, fast variance):
    f32 first and second moment, one ``pmean`` for both, clamped at 0."""
    y = y.astype(jnp.float32)
    axes = tuple(range(y.ndim - 1))
    squares = lax.square(y)  # before the mean, as flax traces them
    mu, mu2 = y.mean(axes), squares.mean(axes)
    if axis_name is not None:
        mu, mu2 = lax.pmean(jnp.stack((mu, mu2)), axis_name)
    return mu, jnp.maximum(0.0, mu2 - lax.square(mu))


def _normalize(y, mean, var, scale, bias, epsilon, dtype):
    """``nn.BatchNorm``'s normalise of ``y`` by a given statistic (flax's
    ``_normalize``, op for op and shape for shape), with the per-channel
    ``mul`` it scaled by."""
    axes, feature = tuple(range(y.ndim - 1)), (1,) * (y.ndim - 1) + (-1,)
    mul = lax.rsqrt(jnp.expand_dims(var, axes) + epsilon)
    mul = mul * scale.reshape(feature)
    z = (y - jnp.expand_dims(mean, axes)) * mul
    return (z + bias.reshape(feature)).astype(dtype), mul.reshape(-1)


def _stat_from_input(moments, rows, kernel, axis_name):
    """The same statistic of ``y = conv1x1(h, kernel)`` without ``y``: over
    the ``M`` rows of ``h``, ``mean(y) = mean(h) @ W`` and ``E[y²] =
    diag(Wᵀ G W)`` with ``G = hᵀh / M`` — a ``[K, K]`` matrix over the
    convolution's input, a quarter of its output's width. ``moments`` are
    ``(Σ h, hᵀh)`` in f32 over the ``rows`` rows of the ``h`` the convolution
    reads (in its dtype), ``kernel`` what it reads too; the small products
    run at full f32 precision, so only where rounding to bf16 falls differs
    from :func:`_stat_from_output`. Both moments are linear in the rows, so
    their ``pmean`` is the synced statistic, exactly."""
    s, gram = moments
    k = s.shape[0]
    w = kernel.reshape(k, -1).astype(jnp.float32)
    s, gram = s / rows, gram / rows
    if axis_name is not None:
        synced = lax.pmean(jnp.concatenate((s[None], gram)), axis_name)
        s, gram = synced[0], synced[1:]
    mu = jnp.dot(s, w, precision=lax.Precision.HIGHEST)
    mu2 = jnp.einsum("kc,kl,lc->c", w, gram, w,
                     precision=lax.Precision.HIGHEST)
    return mu, jnp.maximum(0.0, mu2 - lax.square(mu))


def _closing_unit(dtype, epsilon, axis_name):
    """``(y2, shortcut, scale2, bias2, kernel, scale, bias) → (out, stat2,
    stat)``: a ``Bottleneck`` from ``conv2``'s raw output on — train-mode
    BatchNorm + ReLU, the closing 1×1 convolution, train-mode BatchNorm,
    shortcut add and ReLU — with the two batch statistics ``(mean, var)`` it
    normalised by.

    One algorithm, two exact ways to the closing statistic, chosen by what
    the pass needs. Nothing differentiates it (the scoring forward): the
    statistic comes from the moments of the convolution's input ``h``, scale
    and shift are known before the convolution runs, and XLA writes the
    block's output once, from the convolution's own epilogue. Those moments
    are one kernel's one read of the raw ``y2`` (``ops.input_moments_pallas``
    normalises and clips each tile itself), which is why the unit begins
    there: the closing convolution is the map's only other reader, with the
    same normalise + ReLU as its prologue, and ``h`` is never written. Under
    ``jax.grad`` BatchNorm's backward needs the convolution's raw output
    anyway, so the ``custom_vjp`` rule is the vjp of the plain form —
    ``nn.BatchNorm``, ReLU, ``nn.Conv`` then ``nn.BatchNorm``'s arithmetic,
    each statistic from the map it normalises — and the differentiated pass
    is what it was. The statistics leave for the running averages, which
    nothing differentiates."""

    def form(from_input):
        def apply(y2, shortcut, scale2, bias2, kernel, scale, bias):
            stat2 = _stat_from_output(y2, axis_name)
            z2, mul2 = _normalize(y2, *stat2, scale2, bias2, epsilon, dtype)
            hc, kc = nn.relu(z2), kernel.astype(dtype)
            y = lax.conv_general_dilated(
                hc, kc, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            if from_input:
                moments = input_moments_pallas(
                    y2, stat2[0], mul2, bias2.astype(jnp.float32), dtype)
                stat = _stat_from_input(moments, y2.size // y2.shape[-1], kc,
                                        axis_name)
            else:
                stat = _stat_from_output(y, axis_name)
            z, _ = _normalize(y, *stat, scale, bias, epsilon, dtype)
            return (nn.relu(shortcut + z), lax.stop_gradient(stat2),
                    lax.stop_gradient(stat))
        return apply

    plain = form(from_input=False)
    unit = jax.custom_vjp(form(from_input=True))
    unit.defvjp(lambda *args: jax.vjp(plain, *args),
                lambda vjp, cotangents: vjp(cotangents))
    return unit


class Bottleneck(nn.Module):
    """1×1-3×3-1×1 bottleneck, expansion 4 (``pytorch_model.py:39-64``)."""

    filters: int
    strides: int
    conv: ModuleDef
    norm: ModuleDef
    expansion: int = 4

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters, (3, 3), strides=(self.strides, self.strides))(y)
        norm2 = self.norm()
        conv3 = self.conv(self.filters * self.expansion, (1, 1))
        norm3 = self.norm()
        if residual.shape != y.shape[:-1] + (conv3.features,):
            residual = self.conv(
                self.filters * self.expansion, (1, 1), strides=(self.strides, self.strides)
            )(residual)
            residual = self.norm()(residual)
        if norm3.use_running_average or self.is_initializing():
            return nn.relu(residual + norm3(conv3(nn.relu(norm2(y)))))
        # Batch statistic: the closing unit, from ``conv2``'s raw output on,
        # on the three modules' own variables (same tree as the line above
        # creates and reads).
        self.sow(MOMENT_UNITS, "units", 1)
        weights2, weights3 = (norm.variables["params"] for norm in (norm2, norm3))
        out, stat2, stat3 = _closing_unit(
            norm3.dtype, norm3.epsilon, norm3.axis_name
        )(y, residual, weights2["scale"], weights2["bias"],
          conv3.variables["params"]["kernel"],
          weights3["scale"], weights3["bias"])
        if norm3.is_mutable_collection("batch_stats"):
            for norm, stat in ((norm2, stat2), (norm3, stat3)):
                for name, value in zip(("mean", "var"), stat):
                    norm.put_variable(
                        "batch_stats", name,
                        norm.momentum * norm.get_variable("batch_stats", name)
                        + (1 - norm.momentum) * value)
        return out


class ResNet(nn.Module):
    """CIFAR-stem ResNet (``pytorch_model.py:67-97``)."""

    stage_sizes: Sequence[int]
    block_cls: Callable
    num_classes: int = 10
    num_filters: int = 64
    compute_dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    bn_axis_name: Optional[str] = None  # "data" → cross-replica synced BN

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(
            nn.Conv,
            use_bias=False,
            dtype=self.compute_dtype,
            param_dtype=self.param_dtype,
        )
        norm = partial(
            nn.BatchNorm,
            use_running_average=not train,
            momentum=0.9,
            epsilon=1e-5,
            dtype=self.compute_dtype,
            param_dtype=self.param_dtype,
            axis_name=self.bn_axis_name if train else None,
        )
        x = x.astype(self.compute_dtype)
        # CIFAR stem: 3×3 conv, stride 1, no maxpool (pytorch_model.py:72-73)
        x = conv(self.num_filters, (3, 3))(x)
        x = norm()(x)
        x = nn.relu(x)
        for i, n_blocks in enumerate(self.stage_sizes):  # strides 1/2/2/2 (:74-77)
            for j in range(n_blocks):
                strides = 2 if i > 0 and j == 0 else 1
                x = self.block_cls(
                    filters=self.num_filters * 2**i, strides=strides, conv=conv, norm=norm
                )(x)
        x = jnp.mean(x, axis=(1, 2))  # global avg pool (≡ 4×4 avg pool, :94)
        x = nn.Dense(
            self.num_classes, dtype=self.compute_dtype, param_dtype=self.param_dtype
        )(x)
        return x.astype(jnp.float32)  # logits in fp32 for stable loss/softmax


# Depth configs (``pytorch_model.py:100-113``).
ResNet18 = partial(ResNet, stage_sizes=[2, 2, 2, 2], block_cls=BasicBlock)
ResNet34 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=BasicBlock)
ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=Bottleneck)
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3], block_cls=Bottleneck)
ResNet152 = partial(ResNet, stage_sizes=[3, 8, 36, 3], block_cls=Bottleneck)
