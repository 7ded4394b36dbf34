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

A third kernel serves the model, not the sampler:

3. :func:`input_moments_pallas` — the first and second moments of a
   ``Bottleneck``'s activated ``conv2`` map (``Σ h``, ``hᵀh``) from one read
   of the convolution's RAW output: BatchNorm's normalise + ReLU run on each
   tile in VMEM, the Gram product on the MXU, and ``h`` never reaches HBM
   (``models/resnet.py::_stat_from_input``; PERF.md §6, PR 32).

A fourth serves the loss seam of rows of per-token labels:

4. :func:`head_nll_pallas` — a sequence's token loss and hits from blocks of
   the vocabulary: a grid of (token blocks, vocabulary blocks) forms one
   float32 tile of ``hidden @ head`` in VMEM and folds it, lane by lane,
   into a running log-sum-exp, the label's logit and a running argmax, so
   the ``[T, V]`` logits never reach HBM
   (``sampling/importance.py::sequence_loss`` where nothing differentiates
   the pass; PERF.md §6, PR 45).

A fifth serves a decoder's attention:

5. :func:`rope_heads_pallas` — an operand of the attention kernel from its
   float32 product in one pass: a grid of (token blocks, heads) reads the
   ``[T, heads x hd]`` product a head's block at a time, rotates it (where
   the layer rotates), scales it (queries), casts it and writes it head-major,
   ``[heads, T, hd]``; the transpose is the two ``BlockSpec``s' index maps. Its
   ``custom_vjp`` is the same kernel the other way (``models/decoder.py::
   grouped_query_attention``; PERF.md §6, PR 47).

Uniform variates are passed in (from ``jax.random``) rather than drawn with
the in-kernel TPU PRNG, so the draw is reproducible from a JAX key and the
kernels run identically under ``interpret=True`` on CPU (how the test suite
exercises them without a chip).

Kernels 1 and 2 are a single block each, no grid; 3 to 5 run over one. The
draw kernel holds its scores lane-dense — ``[N/128, 128]`` f32, 4 bytes per candidate — because Mosaic
tiles an ``[N, 1]`` f32 column ``(8, 128)``, 512 bytes per candidate: a
50,000-slot table is 0.2 MB lane-dense and 24 MB as a column, past the
16 MB scoped-VMEM limit of a v5e. ``tests/test_tpu_aot.py`` compiles every
kernel for the v5e target at the shapes ``Trainer`` produces.
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


# ----------------------------------------------------------------- kernel 3
#: Bytes of the raw map one grid step brings into VMEM (twice that with
#: Pallas's double buffering); the f32 intermediates live a chunk at a time.
#: On the v5e 4 MiB blocks run the 64-wide maps 15 % faster than 1 MiB ones.
_MOMENTS_BLOCK_BYTES = 4 << 20
#: Lanes (batch-in-lanes view) or rows (channels-in-lanes view) activated
#: and contracted at a time inside a grid step. The row loop is unrolled as
#: the kernel is lowered (a rolled one serialises on the accumulator: 3x
#: slower at 256 rows), and every trace of the step pays for that: 64
#: chunks of 256 rows a block cost 16 s of warm set-up for 0.1 ms a step
#: (PERF.md section 6, PR 32), so 1,024.
_MOMENTS_LANE_CHUNK = 256
_MOMENTS_ROW_CHUNK = 1024


def _activate(y, mean, mul, bias, dtype):
    """BatchNorm's normalise + ReLU on a tile of the raw map, in flax's
    arithmetic (``_normalize``): f32, cast to ``dtype``, then ``max 0``."""
    z = (y.astype(jnp.float32) - mean) * mul + bias
    return jnp.maximum(z.astype(dtype), 0)


def _zero_at_first_step(*refs):
    """The accumulators stay in VMEM across the grid: clear them once."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        for ref in refs:
            ref[...] = jnp.zeros_like(ref)


def _moments_lanes_kernel(y_ref, mean_ref, mul_ref, bias_ref, s_ref, g_ref,
                          *, dtype, chunk):
    """``y_ref``: ``[P, C, N]`` — ``P`` groups of spatial positions, ``C``
    channels (of one or several stacked positions) in the sublanes, the
    batch in the lanes. Accumulates ``s [C, 1] = Σ h`` and ``g [C, C] =
    h hᵀ`` (contracting the lanes) over the grid; ``mean``/``mul``/``bias``
    are ``[C, 1]`` f32."""
    _zero_at_first_step(s_ref, g_ref)
    mean, mul, bias = mean_ref[...], mul_ref[...], bias_ref[...]

    def group(j, carry):
        s, g = jnp.zeros_like(s_ref), jnp.zeros_like(g_ref)
        for lo in range(0, y_ref.shape[2], chunk):
            h = _activate(y_ref[j, :, lo:lo + chunk], mean, mul, bias, dtype)
            g = g + lax.dot_general(h, h, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s + jnp.sum(h.astype(jnp.float32), axis=1, keepdims=True)
        s_ref[...] += s
        g_ref[...] += g
        return carry

    lax.fori_loop(0, y_ref.shape[0], group, 0)


def _moments_sublanes_kernel(y_ref, mean_ref, mul_ref, bias_ref, s_ref, g_ref,
                             *, dtype, chunk, rows):
    """``y_ref``: ``[T, K]`` — rows (position x batch) in the sublanes,
    channels in the lanes. Accumulates ``s [1, K] = Σ h`` and ``g [K, K] =
    hᵀ h`` (contracting the sublanes) over the grid; rows past ``rows`` (the
    last block's padding) count as 0. ``mean``/``mul``/``bias``: ``[1, K]``."""
    _zero_at_first_step(s_ref, g_ref)
    mean, mul, bias = mean_ref[...], mul_ref[...], bias_ref[...]
    t = y_ref.shape[0]
    first = pl.program_id(0) * t

    def add(lo, size):
        h = _activate(y_ref[pl.ds(lo, size), :], mean, mul, bias, dtype)
        if rows % t:
            row = first + lo + lax.broadcasted_iota(jnp.int32, (size, 1), 0)
            h = jnp.where(row < rows, h, jnp.zeros_like(h))
        g_ref[...] += lax.dot_general(h, h, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        s_ref[...] += jnp.sum(h.astype(jnp.float32), axis=0, keepdims=True)

    def whole(i, carry):
        add(pl.multiple_of(i * chunk, chunk), chunk)
        return carry

    if t >= chunk:
        lax.fori_loop(0, t // chunk, whole, 0, unroll=True)
    if t % chunk:           # a map of fewer rows than a block, in one block
        add(t - t % chunk, t % chunk)


def input_moments_pallas(y: jax.Array, mean: jax.Array, mul: jax.Array,
                         bias: jax.Array, dtype) -> Tuple[jax.Array, jax.Array]:
    """``(Σ h [K], hᵀh [K, K])`` in f32 over the rows of ``h = relu(((y −
    mean) · mul + bias).astype(dtype))``, from ONE read of the raw NHWC map
    ``y [N, H, W, K]``: ``h`` is formed tile by tile in VMEM and never
    written. ``mean``/``mul``/``bias``: ``[K]`` f32.

    Both moments are sums over rows, so any row order gives them; the
    kernel takes the map in the order the TPU compiler already holds it in,
    and the view below is then a ``bitcast`` of the convolution's output,
    not a relayout (``tests/test_tpu_aot.py``). Narrow maps (``K`` < 128)
    lie batch-in-lanes, channels in the sublanes: ``[H·W, K, N]``, with
    ``128 // K`` positions stacked so the product fills the MXU's width
    (its diagonal blocks sum to ``G``). Wide maps, and narrow ones whose
    positions cannot be stacked in whole tiles, lie channels-in-lanes:
    ``[H·W·N, K]``."""
    n, hh, ww, k = y.shape
    hw, itemsize = hh * ww, y.dtype.itemsize
    sublanes = 8 * 4 // itemsize          # rows of one packed tile
    stack = _LANES // k if k < _LANES else 0
    if stack and not (_LANES % k or k % sublanes or hw % stack):
        c, groups = stack * k, hw // stack
        view = jnp.transpose(y, (1, 2, 3, 0)).reshape(groups, c, n)
        # the most groups a block may hold that divide them all
        p = max(d for d in range(1, groups + 1) if groups % d == 0
                and d * c * n * itemsize <= max(_MOMENTS_BLOCK_BYTES,
                                                c * n * itemsize))
        kernel = functools.partial(
            _moments_lanes_kernel, dtype=dtype,
            chunk=_MOMENTS_LANE_CHUNK if n % _LANES == 0 else n)
        grid, block, index = groups // p, (p, c, n), lambda i: (i, 0, 0)
        vector, side = (c, 1), c       # per-channel operands: columns
    else:
        stack, rows = 1, hw * n
        view = jnp.transpose(y, (1, 2, 0, 3)).reshape(rows, k)
        t = max(_MOMENTS_ROW_CHUNK,
                _MOMENTS_BLOCK_BYTES // (k * itemsize)
                // _MOMENTS_ROW_CHUNK * _MOMENTS_ROW_CHUNK)
        t = rows if rows <= t else t
        kernel = functools.partial(
            _moments_sublanes_kernel, dtype=dtype, chunk=_MOMENTS_ROW_CHUNK,
            rows=rows)
        grid, block, index = pl.cdiv(rows, t), (t, k), lambda i: (i, 0)
        vector, side = (1, k), k       # per-channel operands: rows
    # under a ``shard_map`` that checks them: the sums vary as the map does
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                            vma=jax.typeof(y).vma)
    whole = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))
    with jax.named_scope("mercury_moments_kernel"):
        s, g = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=[pl.BlockSpec(block, index)] + [whole(vector)] * 3,
            out_specs=(whole(vector), whole((side, side))),
            out_shape=(f32(vector), f32((side, side))),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=_interpret(),
        )(view, *(jnp.tile(v.astype(jnp.float32), stack).reshape(vector)
                  for v in (mean, mul, bias)))
    # the stacked positions' diagonal blocks (one block where none is)
    return (s.reshape(stack, k).sum(0),
            sum(g[i * k:(i + 1) * k, i * k:(i + 1) * k] for i in range(stack)))


# ----------------------------------------------------------------- kernel 4
#: Rows of tokens x columns of the vocabulary of one grid step, which takes
#: the whole contraction. On the v5e, one sequence of 8,192 tokens alone:
#: 4.87 ms at hidden 2,560 x vocabulary 18,992 and 3.37 ms at 2,048 x 16,032
#: (83 / 81 % of the peak; the plain form 6.09 / 4.63), within 1 % of the best
#: of five shapes; 2,048 columns are slower by a quarter (PERF.md §6, PR 45).
HEAD_BLOCKS = (1024, 1024)
#: What a grid step may hold in VMEM: the double-buffered operand blocks,
#: the float32 tile and the fold's temporaries. Past the 16 MiB a kernel is
#: given unasked, inside the v5e's 128 MiB.
_HEAD_VMEM_BYTES = 64 << 20


def _head_vmem_bytes(d: int, itemsize: int, blocks) -> int:
    """Bytes of a grid step: both operand blocks twice (Pallas's double
    buffering), three float32 tiles (the product and the fold's
    temporaries), the four lane-wise carries."""
    rows, columns = blocks
    return (2 * (rows + columns) * d * itemsize + 3 * rows * columns * 4
            + 4 * rows * _LANES * 4)


def head_nll_takes(t: int, d: int, itemsize: int = 2, blocks=None) -> bool:
    """Whether :func:`head_nll_pallas` takes ``t`` tokens of hidden size
    ``d`` in operands of ``itemsize`` bytes: whole blocks of tokens, and a
    contraction whose blocks fit the kernel's VMEM (hidden 2,560 in
    bfloat16: 34 MiB of the 64). Any vocabulary: its last block is masked
    inside the kernel. ``blocks``: :data:`HEAD_BLOCKS` where not given."""
    blocks = blocks or HEAD_BLOCKS
    return (t % blocks[0] == 0
            and _head_vmem_bytes(d, itemsize, blocks) <= _HEAD_VMEM_BYTES)


def _head_nll_kernel(hidden_ref, head_ref, labels_ref, nll_ref, hit_ref,
                     logits_ref, max_ref, sum_ref, picked_ref, arg_ref,
                     *, vocab: int):
    """One ``[rows, columns]`` float32 tile of the logits, folded into what
    is carried a token across the vocabulary blocks (the inner grid axis),
    LANE BY LANE: ``[rows, 128]`` each, lane ``l`` standing for the columns
    ``l mod 128``, so that the fold is elementwise, straight-line code and
    nothing crosses lanes until the last block (a fold that reduces each
    tile across its lanes, or walks it in a loop, costs the kernel 25-50 %
    on the v5e: PERF.md §6, PR 45). Carried: the running maximum, the sum of
    exponentials rescaled to it, the label's logit, and the 128-column
    group the maximum was first seen in. The tile is written to
    ``logits_ref`` and folded from there: folding the product's value as it
    is reads 5 % slower. ``labels_ref`` and the outputs: ``[rows, 1]``."""
    j, last = pl.program_id(1), pl.num_programs(1) - 1
    columns = logits_ref.shape[1]
    groups = columns // _LANES

    @pl.when(j == 0)
    def _():
        # finite, so that a lane that has seen no column yet (or never
        # will: a vocabulary under 128) rescales by exp(0) and not exp(nan)
        max_ref[...] = jnp.full_like(max_ref, jnp.finfo(jnp.float32).min)
        sum_ref[...] = jnp.zeros_like(sum_ref)
        picked_ref[...] = jnp.zeros_like(picked_ref)
        arg_ref[...] = jnp.zeros_like(arg_ref)

    logits_ref[...] = jnp.dot(hidden_ref[...], head_ref[...],
                              preferred_element_type=jnp.float32)

    def fold(valid: int):
        """The tile's first ``valid`` columns (static) into the carries."""
        lane = lax.broadcasted_iota(jnp.int32, max_ref.shape, 1)
        label = labels_ref[...] - j * columns       # within the tile
        tiles = []
        for g in range(pl.cdiv(valid, _LANES)):
            x = logits_ref[:, g * _LANES:(g + 1) * _LANES]
            if (g + 1) * _LANES > valid:    # columns past the head
                x = jnp.where(lane < valid - g * _LANES, x, -jnp.inf)
            tiles.append(x)
        before = best = max_ref[...]
        group = arg_ref[...]
        for g, x in enumerate(tiles):
            # ``>``: the lowest index wins a tie, in and across blocks
            better = x > best
            group = jnp.where(better, j * groups + g, group)
            best = jnp.where(better, x, best)
        total = sum_ref[...] * jnp.exp(before - best)
        picked = picked_ref[...]
        for g, x in enumerate(tiles):
            total = total + jnp.exp(x - best)
            picked = picked + jnp.where(lane + g * _LANES == label, x, 0.0)
        max_ref[...] = best
        arg_ref[...] = group
        sum_ref[...] = total
        picked_ref[...] = picked

    tail = vocab % columns      # columns of the last block, if not whole
    if tail:
        pl.when(j < last)(lambda: fold(columns))
        pl.when(j == last)(lambda: fold(tail))
    else:
        fold(columns)

    @pl.when(j == last)
    def _():
        # across the lanes, once a token block
        lanes_max = max_ref[...]
        best = jnp.max(lanes_max, axis=1, keepdims=True)
        total = jnp.sum(sum_ref[...] * jnp.exp(lanes_max - best), axis=1,
                        keepdims=True)
        nll_ref[...] = (best + jnp.log(total)
                        - jnp.sum(picked_ref[...], axis=1, keepdims=True))
        column = arg_ref[...] * _LANES + lax.broadcasted_iota(
            jnp.int32, lanes_max.shape, 1)
        first = jnp.min(jnp.where(lanes_max == best, column, vocab), axis=1,
                        keepdims=True)
        hit_ref[...] = (first == labels_ref[...]).astype(jnp.float32)


def head_nll_pallas(hidden: jax.Array, head: jax.Array, labels: jax.Array,
                    blocks=None) -> Tuple[jax.Array, jax.Array]:
    """``(nll [T], hit [T])`` in f32 of one sequence: the token negative
    log-likelihood ``logsumexp(z_t) − z_t[y_t]`` and whether ``argmax(z_t)
    == y_t`` (lowest index on a tie, as ``jnp.argmax``), for the float32
    logits ``z = hidden [T, D] @ head [D, V]`` of the operands as they are
    given (bfloat16 on the cells) — which are formed a ``blocks[0] x
    blocks[1]`` tile at a time in VMEM and never written. ``labels [T]``
    int, each in ``[0, V)``. Shapes: :func:`head_nll_takes`. Not
    differentiable: the seam's ``custom_vjp`` gives the differentiated pass
    the plain form (``sampling/importance.py``)."""
    (t, d), v = hidden.shape, head.shape[1]
    rows, columns = blocks = blocks or HEAD_BLOCKS
    if (not head_nll_takes(t, d, hidden.dtype.itemsize, blocks) or rows % 8
            or columns % _LANES):
        raise ValueError(f"{t} tokens of hidden size {d} are not whole "
                         f"blocks of {rows} x {columns} within the kernel's "
                         f"VMEM, or these no whole tiles of 8 x {_LANES}")
    # under a ``shard_map`` that checks them: a token's numbers vary as it does
    column = jax.ShapeDtypeStruct((t, 1), jnp.float32,
                                  vma=jax.typeof(hidden).vma)
    token = pl.BlockSpec((rows, 1), lambda i, j: (i, 0))
    carry = functools.partial(pltpu.VMEM, (rows, _LANES))
    nll, hit = pl.pallas_call(
        functools.partial(_head_nll_kernel, vocab=v),
        grid=(t // rows, pl.cdiv(v, columns)),
        in_specs=[pl.BlockSpec((rows, d), lambda i, j: (i, 0)),
                  pl.BlockSpec((d, columns), lambda i, j: (0, j)),
                  token],
        out_specs=(token, token),
        out_shape=(column, column),
        scratch_shapes=[pltpu.VMEM((rows, columns), jnp.float32),
                        carry(jnp.float32), carry(jnp.float32),
                        carry(jnp.float32), carry(jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_HEAD_VMEM_BYTES),
        name="mercury_head_nll",
        interpret=_interpret(),
    )(hidden, head, labels.reshape(t, 1).astype(jnp.int32))
    return nll[:, 0], hit[:, 0]


# ----------------------------------------------------------------- kernel 5
#: Tokens of one grid step of :func:`rope_heads_pallas` (a head's block of
#: the product, the two tables' and the operand's: 3.5 MiB double-buffered
#: at heads of 128).
ROPE_BLOCK = 1024


def rope_heads_takes(head_dim: int, rope_dim: int) -> bool:
    """Whether :func:`rope_heads_pallas` takes heads of ``head_dim`` of
    which ``rope_dim`` columns are rotated: a head is whole lanes (its
    columns of the flat product are a block of their own), rotated whole or
    not at all (a roll by half the head pairs the columns). Any number of
    tokens and of heads."""
    return head_dim % _LANES == 0 and rope_dim in (0, head_dim)


def _rope_heads_kernel(x_ref, *refs, scale: float, transposed: bool):
    """One head's ``[rows, hd]`` block in float32: ``y = (x * cos + roll(x,
    hd/2) * sin) * scale`` with ``sin`` signed (minus on the first half), or,
    ``transposed``, the same linear map's transpose ``dx = g' * cos +
    roll(g' * sin, hd/2)`` of ``g' = g * scale``: the order of operations of
    ``rotate_half``, the scale and the cast, and of their autodiff. No
    tables: no rotation."""
    *tables, out_ref = refs
    x = x_ref[...].astype(jnp.float32)
    if transposed and scale != 1.0:
        x = x * scale
    if tables:
        cos, sin = (ref[...] for ref in tables)
        half = x.shape[-1] // 2
        if transposed:
            x = x * cos + pltpu.roll(x * sin, half, 1)
        else:
            x = x * cos + pltpu.roll(x, half, 1) * sin
    if not transposed and scale != 1.0:
        x = x * scale
    out_ref[...] = x.astype(out_ref.dtype)


def _rope_heads_call(x, tables, head_dim: int, scale: float, dtype,
                     transposed: bool):
    """The kernel one way (``x [T, heads x hd]`` -> ``[heads, T, hd]``) or
    the other (``transposed``): which side is flat and which head-major is
    the index maps' alone."""
    tables = tuple(tables or ())
    if (not rope_heads_takes(head_dim, tables[0].shape[1] if tables else 0)
            or x.shape[-1] % head_dim):
        raise ValueError(
            f"{x.shape[-1]} columns in heads of {head_dim}, the tables "
            f"{[a.shape for a in tables]}: not whole heads of whole lanes "
            f"({_LANES}) rotated whole or not at all")
    if transposed:
        heads, t, _ = x.shape
        out_shape = (t, heads * head_dim)
    else:
        t, heads = x.shape[0], x.shape[1] // head_dim
        out_shape = (heads, t, head_dim)
    rows = min(ROPE_BLOCK, t)
    flat = pl.BlockSpec((rows, head_dim), lambda i, h: (i, h))
    major = pl.BlockSpec((None, rows, head_dim), lambda i, h: (h, i, 0))
    # the heads are the inner axis: a token block's tables are read once
    table = pl.BlockSpec((rows, head_dim), lambda i, h: (i, 0))
    return pl.pallas_call(
        functools.partial(_rope_heads_kernel, scale=scale,
                          transposed=transposed),
        grid=(pl.cdiv(t, rows), heads),
        in_specs=[major if transposed else flat] + [table] * len(tables),
        out_specs=flat if transposed else major,
        # under a ``shard_map`` that checks them: it varies as the product does
        out_shape=jax.ShapeDtypeStruct(out_shape, dtype,
                                       vma=jax.typeof(x).vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="mercury_rope_heads",
        interpret=_interpret(),
    )(x, *tables)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def rope_heads_pallas(x: jax.Array, tables, head_dim: int, scale: float,
                      dtype) -> jax.Array:
    """An operand of the attention kernel, ``[heads, T, hd]`` in ``dtype``,
    from ONE read of its float32 product ``x [T, heads x hd]``: rotated
    (rotate-half over the whole head) by ``tables = (cos, signed sin)``, each
    ``[T, hd]`` float32 (``models/decoder.py::rope_tables``; None: no
    rotation), times ``scale``, cast; all in float32 before the cast, in
    ``rotate_half``'s order of operations. Shapes: :func:`rope_heads_takes`.
    The map is linear: its gradient is the same kernel the other way, float32
    ``[T, heads x hd]`` from the cotangent in ``dtype``, and keeps the tables
    alone."""
    return _rope_heads_call(x, tables, head_dim, scale, dtype, False)


def _rope_heads_fwd(x, tables, head_dim, scale, dtype):
    return _rope_heads_call(x, tables, head_dim, scale, dtype, False), tables


def _rope_heads_bwd(head_dim, scale, dtype, tables, g):
    dx = _rope_heads_call(g, tables, head_dim, scale, jnp.float32, True)
    return dx, jax.tree.map(jnp.zeros_like, tables)


rope_heads_pallas.defvjp(_rope_heads_fwd, _rope_heads_bwd)
