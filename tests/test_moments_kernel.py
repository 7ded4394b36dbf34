"""The closing unit's input moments from one read of ``conv2``'s raw map
(``ops/mercury_kernels.py::input_moments_pallas``, PR 32).

The kernel is handed the RAW map with BatchNorm's ``(mean, mul, bias)`` and
forms ``h = relu(normalise(y))`` tile by tile; the yardstick here is XLA's
two passes over the materialised ``h`` — ``rows.sum(0)`` and the
``dot_general`` — which the kernel replaced. Off the chip the kernel runs in
Pallas's interpreter, so the cases are small; Mosaic's verdict on the real
shapes is ``tests/test_tpu_aot.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mercury_tpu.ops import mercury_kernels
from mercury_tpu.ops.mercury_kernels import input_moments_pallas


def _case(shape, dtype):
    """A raw map off the symmetric point and a statistic to normalise it
    by: ``(y, mean, mul, bias)``."""
    k = shape[-1]
    keys = jax.random.split(jax.random.key(sum(shape)), 4)
    y = (2.0 * jax.random.normal(keys[0], shape) + 0.3).astype(dtype)
    mean = 0.3 * jax.random.normal(keys[1], (k,))
    mul = 1.0 + 0.1 * jax.random.normal(keys[2], (k,))
    bias = 0.2 * jax.random.normal(keys[3], (k,))
    return y, mean, mul, bias


def _two_passes(y, mean, mul, bias, dtype):
    """``(Σ h, hᵀh)`` as the parent took them: the materialised ``h`` (flax's
    normalise, cast, ReLU), a row sum and a Gram product, in f32."""
    h = jnp.maximum(
        ((y.astype(jnp.float32) - mean) * mul + bias).astype(dtype), 0)
    rows = h.reshape(-1, h.shape[-1])
    gram = lax.dot_general(rows, rows, (((0,), (0,)), ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
    return rows.astype(jnp.float32).sum(0), gram


def _assert_moments(got, want, rtol=2e-6, atol_s=1e-4):
    """``(s, gram)`` against the two passes': the same ``h`` to the bit, so
    only the order of the f32 sums differs."""
    (s, gram), (want_s, want_gram) = got, want
    np.testing.assert_allclose(s, want_s, rtol=rtol, atol=atol_s)
    np.testing.assert_allclose(gram, want_gram, rtol=rtol,
                               atol=rtol * float(jnp.abs(want_gram).max()))


def _views(shape):
    """The operand view the kernel takes a map of this shape in: the shape
    of the map as each ``pallas_call`` of the traced wrapper is handed it."""
    args = _case(shape, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda *a: input_moments_pallas(*a, jnp.bfloat16))(*args)
    return [e.invars[0].aval.shape for e in jaxpr.eqns
            if e.primitive.name == "pallas_call"]


DTYPES = [pytest.param(jnp.bfloat16, id="bf16"),
          pytest.param(jnp.float32, id="f32")]
# the cell's four widths; both views of the 64-wide one
SHAPES = [
    pytest.param((16, 8, 8, 64), id="K64-batch-in-lanes"),
    pytest.param((4, 3, 3, 64), id="K64-odd-positions-batch-in-sublanes"),
    pytest.param((16, 8, 8, 128), id="K128"),
    pytest.param((16, 4, 4, 256), id="K256"),
    pytest.param((16, 4, 4, 512), id="K512"),
    pytest.param((4, 4, 4, 32), id="K32-four-positions-stacked"),
    pytest.param((3, 5, 5, 8), id="K8-narrower-than-a-bf16-tile"),
]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_moments_are_the_two_passes(shape, dtype):
    args = _case(shape, dtype)
    s, gram = jax.jit(lambda *a: input_moments_pallas(*a, dtype))(*args)
    assert s.shape == shape[-1:] and gram.shape == shape[-1:] * 2
    assert s.dtype == gram.dtype == jnp.float32
    _assert_moments((s, gram), _two_passes(*args, dtype))
    assert np.array_equal(gram, gram.T)


def test_view_follows_the_maps_width():
    """Narrow maps go batch-in-lanes with positions stacked to the MXU's
    width, wide ones (and what cannot be stacked) channels-in-lanes: one
    call either way."""
    assert _views((16, 8, 8, 64)) == [(32, 128, 16)]   # [H*W/2, 2K, N]
    assert _views((4, 4, 4, 32)) == [(4, 128, 4)]      # [H*W/4, 4K, N]
    assert _views((4, 3, 3, 64)) == [(36, 64)]         # 9 positions: no pairs
    for k in (128, 256, 512):
        assert _views((16, 4, 4, k)) == [(256, k)]     # [H*W*N, K]


def _grids(fn, *args):
    """The grid of each ``pallas_call`` in ``fn``'s trace."""
    return [e.params["grid_mapping"].grid
            for e in jax.make_jaxpr(fn)(*args).eqns
            if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("dtype", DTYPES)
def test_rows_past_the_last_whole_tile_count_once(dtype, monkeypatch):
    """A row count that is no multiple of the tile: the last block's padding
    adds nothing, though ``relu(bias)`` of a padded row would not be 0."""
    monkeypatch.setattr(mercury_kernels, "_MOMENTS_BLOCK_BYTES", 1 << 16)
    monkeypatch.setattr(mercury_kernels, "_MOMENTS_ROW_CHUNK", 128)
    shape = (5, 9, 9, 128)            # 405 rows in tiles of 256 (128 at f32)
    args = _case(shape, dtype)
    moments = lambda *a: input_moments_pallas(*a, dtype)
    tile = (1 << 16) // (128 * jnp.dtype(dtype).itemsize)
    assert _grids(moments, *args) == [(-(-405 // tile),)] and 405 % tile
    _assert_moments(moments(*args), _two_passes(*args, dtype))


@pytest.mark.parametrize("shape", [(8, 16, 16, 64), (520, 2, 2, 128)],
                         ids=["batch-in-lanes", "batch-in-sublanes"])
def test_sums_carry_across_the_grid(shape, monkeypatch):
    """Several grid steps (blocks of 16 KiB here): the f32 sums accumulate
    over all of them, and the last, partial block of the second case is
    masked."""
    monkeypatch.setattr(mercury_kernels, "_MOMENTS_BLOCK_BYTES", 1 << 14)
    monkeypatch.setattr(mercury_kernels, "_MOMENTS_ROW_CHUNK", 64)
    args = _case(shape, jnp.bfloat16)
    # 2,048 contractions of 8 lanes each, added one after the other in f32:
    # a few 1e-6 of the largest sum (against float64: 7e-6; XLA's one pass 3e-7)
    _assert_moments(input_moments_pallas(*args, jnp.bfloat16),
                    _two_passes(*args, jnp.bfloat16), rtol=2e-5, atol_s=1e-3)
