"""Pallas TPU kernels for the Mercury hot ops.

Two kernels cover the importance-sampling inner loop (the math of
``Trainer.update_samples``, ``pytorch_collab.py:101-117``):

1. :func:`per_sample_nll_pallas` — fused per-sample cross-entropy
   (log-softmax + label gather in one VMEM pass, ≡ ``F.cross_entropy(...,
   reduction='none')`` at ``:102,:133``), with a custom VJP
   (``softmax − onehot`` per sample) so it serves both the scoring pass and
   the differentiable training loss.
2. :func:`score_and_draw_pallas` — fused score smoothing → normalization →
   inverse-CDF categorical draws (≡ ``:111-116``), one VMEM-resident
   kernel: the cumulative distribution never round-trips to HBM. It serves
   the pool sampler (N = the candidate pool) and the scoretable sampler
   (N = the whole shard table, after the step's jax-native decay and
   refresh scatter).

Uniform variates are passed in (from ``jax.random``) rather than drawn with
the in-kernel TPU PRNG, so the draw is reproducible from a JAX key and the
kernels run identically under ``interpret=True`` on CPU (how the test suite
exercises them without a chip).

Each kernel is a single block, no grid. The draw kernel holds its scores
lane-dense — ``[N/128, 128]`` f32, 4 bytes per candidate — because Mosaic
tiles an ``[N, 1]`` f32 column ``(8, 128)``, 512 bytes per candidate: a
50,000-slot table is 0.2 MB lane-dense and 24 MB as a column, past the
16 MB scoped-VMEM limit of a v5e. ``tests/test_tpu_aot.py`` compiles both
kernels for the v5e target at the shapes ``Trainer`` produces.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def on_tpu() -> bool:
    """True when the default backend is a real TPU (kernels compile via
    Mosaic); otherwise wrappers run in interpret mode."""
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not on_tpu()


# ----------------------------------------------------------------- kernel 1
def _nll_kernel(logits_ref, labels_ref, nll_ref):
    """Fused log-softmax + one-hot gather: nll_i = lse(logits_i) − logits_i[y_i]."""
    logits = logits_ref[:].astype(jnp.float32)          # [N, C]
    m = jnp.max(logits, axis=1, keepdims=True)
    shifted = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=1, keepdims=True)) + m  # [N, 1]
    n, c = logits.shape
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, (n, c), 1) == labels_ref[:]
    ).astype(jnp.float32)                                # labels_ref: [N, 1]
    picked = jnp.sum(logits * onehot, axis=1, keepdims=True)  # [N, 1]
    nll_ref[:] = lse - picked


def _nll_fwd_raw(logits: jax.Array, labels: jax.Array) -> jax.Array:
    n, _ = logits.shape
    return pl.pallas_call(
        _nll_kernel,
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=_interpret(),
    )(logits, labels.reshape(-1, 1).astype(jnp.int32))[:, 0]


def _nll_bwd_kernel(logits_ref, labels_ref, g_ref, grad_ref):
    """d nll_i / d logits_i = softmax(logits_i) − onehot(y_i), scaled by g_i."""
    logits = logits_ref[:].astype(jnp.float32)
    m = jnp.max(logits, axis=1, keepdims=True)
    e = jnp.exp(logits - m)
    softmax = e / jnp.sum(e, axis=1, keepdims=True)
    n, c = logits.shape
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, (n, c), 1) == labels_ref[:]
    ).astype(jnp.float32)
    grad_ref[:] = (softmax - onehot) * g_ref[:]          # g_ref: [N, 1]


@jax.custom_vjp
def per_sample_nll_pallas(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Fused per-sample cross-entropy (``reduction='none'``) as a Pallas
    kernel. ``logits``: [N, C] (any float dtype), ``labels``: [N] int.
    Returns fp32 ``[N]`` losses. Differentiable w.r.t. logits.

    Runs under the ``mercury_nll_kernel`` named scope — the jaxpr auditor
    (``mercury_tpu/lint/audit.py``) keys per-region checks on these
    anchors when a TPU plan traces the Pallas path."""
    with jax.named_scope("mercury_nll_kernel"):
        return _nll_fwd_raw(logits, labels)


def _vjp_fwd(logits, labels):
    return _nll_fwd_raw(logits, labels), (logits, labels)


def _vjp_bwd(residual, g):
    logits, labels = residual
    n, _ = logits.shape
    grad = pl.pallas_call(
        _nll_bwd_kernel,
        out_shape=jax.ShapeDtypeStruct(logits.shape, jnp.float32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=_interpret(),
    )(logits, labels.reshape(-1, 1).astype(jnp.int32),
      g.reshape(-1, 1).astype(jnp.float32))
    return grad.astype(logits.dtype), None


per_sample_nll_pallas.defvjp(_vjp_fwd, _vjp_bwd)


# ----------------------------------------------------------------- kernel 2
_LANES = 128
#: Rows per CDF chunk: the row-prefix triangle is ``[T, T]`` f32, 1 MB at
#: 512, and one chunk covers 65,536 candidates.
_ROW_CHUNK = 512


def _score_draw_kernel(
    scores_ref, ema_ref, uniforms_ref, probs_ref, selected_ref, cdf_ref,
    *, alpha: float, true_n: int,
):
    """score → normalize → inverse-CDF draw, all in VMEM.

    ``scores_ref``: [M, 128] raw per-candidate scores, row-major, padded
    past ``true_n``; ``ema_ref``: [1] (SMEM); ``uniforms_ref``: [B] iid
    U(0,1) (SMEM). Outputs: normalized probs [M, 128] (exactly 0 on the
    padding), drawn candidate positions [B] int32 (SMEM). ``cdf_ref`` is
    an [M, 128] VMEM scratch.

    Mosaic notes: ``cumsum`` has no TC lowering, so the CDF is two
    triangular matmuls (MXU) — within each 128-lane row, then an exclusive
    prefix over the row totals, in chunks of ``_ROW_CHUNK`` rows with the
    running total carried as a scalar (a static Python unroll). The
    operands are 0/1 masks, so at HIGHEST precision every product is exact
    and the CDF differs from a sequential f32 cumsum only by summation
    order. Inverse-CDF sampling ≡ multinomial-with-replacement (:114):
    ``idx_b = #{j : cdf_j <= u_b}``, one masked count over the table per
    draw.
    """
    m = scores_ref.shape[0]
    slot = (lax.broadcasted_iota(jnp.int32, (m, _LANES), 0) * _LANES
            + lax.broadcasted_iota(jnp.int32, (m, _LANES), 1))
    scores = jnp.where(
        slot < true_n,
        jnp.maximum(scores_ref[:] + alpha * ema_ref[0], 1e-12),   # :111
        0.0,
    )
    probs_ref[:] = scores / jnp.sum(scores)                      # :112

    dot = functools.partial(jnp.dot, precision=lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    upper = (lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
             <= lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
             ).astype(jnp.float32)
    t = min(m, _ROW_CHUNK)
    strict_lower = (lax.broadcasted_iota(jnp.int32, (t, t), 1)
                    < lax.broadcasted_iota(jnp.int32, (t, t), 0)
                    ).astype(jnp.float32)
    prefix = jnp.zeros((), jnp.float32)
    for c in range(m // t):
        rows = slice(c * t, (c + 1) * t)
        row_cdf = dot(probs_ref[rows, :], upper)                  # [T, 128]
        row_total = row_cdf[:, _LANES - 1:]                       # [T, 1]
        cdf_ref[rows, :] = row_cdf + (prefix + dot(strict_lower, row_total))
        prefix = prefix + jnp.sum(row_total)

    def draw(b, carry):
        below = (cdf_ref[:] <= uniforms_ref[b]).astype(jnp.int32)
        # Clamp to the REAL pool: the padding's CDF is flat at ~1.0, so a
        # u within rounding of 1 could otherwise count past the last slot.
        selected_ref[b] = jnp.minimum(jnp.sum(below), true_n - 1)
        return carry

    lax.fori_loop(0, uniforms_ref.shape[0], draw, 0)


def score_and_draw_pallas(
    key: jax.Array,
    losses: jax.Array,
    ema_value: jax.Array,
    batch_size: int,
    alpha: float = 0.5,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused Mercury selection given per-candidate losses and the (already
    updated, possibly psum-synced) EMA value.

    Returns ``(probs [N], selected [B] int32, scaled_probs [B])`` matching
    the jax-native ``importance_probs`` + ``draw_with_replacement`` +
    ``p·N`` pipeline (``mercury_tpu.sampling.importance``). ``p·N`` is a
    ``B``-element gather of the kernel's probs, left to XLA; N is the REAL
    candidate count — the reweight contract (:116) is about the pool the
    caller drew from, not the padded tile.
    """
    n = losses.shape[0]
    # Whole (8, 128) f32 tiles; past one CDF chunk, whole chunks.
    block = 8 * _LANES if n <= _ROW_CHUNK * _LANES else _ROW_CHUNK * _LANES
    n_pad = -(-n // block) * block
    m = n_pad // _LANES
    scores = jnp.pad(losses.astype(jnp.float32), (0, n_pad - n))
    uniforms = jax.random.uniform(key, (batch_size,), jnp.float32)
    kernel = functools.partial(_score_draw_kernel, alpha=alpha, true_n=n)
    # Auditor anchor (see per_sample_nll_pallas): the fused selection
    # kernel is one named region in the traced program.
    with jax.named_scope("mercury_score_draw_kernel"):
        probs, selected = pl.pallas_call(
            kernel,
            out_shape=(
                jax.ShapeDtypeStruct((m, _LANES), jnp.float32),
                jax.ShapeDtypeStruct((batch_size,), jnp.int32),
            ),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=(
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ),
            scratch_shapes=[pltpu.VMEM((m, _LANES), jnp.float32)],
            interpret=_interpret(),
        )(
            scores.reshape(m, _LANES),
            ema_value.reshape(1).astype(jnp.float32),
            uniforms,
        )
    probs = probs.reshape(n_pad)[:n]
    return probs, selected, probs[selected] * n
