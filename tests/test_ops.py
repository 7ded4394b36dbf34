"""Pallas kernel tests (interpret mode on CPU): fused per-sample CE must
match the jax-native version bit-for-bit-ish, its VJP must match autodiff,
and the fused score/draw must match the importance pipeline — probs to
rounding, draws to an inverse-CDF reference on the same uniforms. The
fused uint8 ingest chain (jax-native) must match the unfused
normalize→augment chain bit-for-bit at f32. The head's kernel over
vocabulary blocks must give the plain form's token loss to rounding and its
hits exactly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mercury_tpu.data.pipeline import (
    augment_batch,
    augment_normalize,
    crop_flip_draws,
    normalize_images,
    select_crop_flip,
)
from mercury_tpu.ops import (
    head_nll_pallas,
    head_nll_takes,
    per_sample_nll_pallas,
    score_and_draw_pallas,
)
from mercury_tpu.sampling.importance import (
    _token_rows_plain,
    importance_probs,
    per_sample_loss,
)


@pytest.fixture(scope="module")
def logits_labels():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(0, 3, (64, 10)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, 64), jnp.int32)
    return logits, labels


class TestPerSampleNLL:
    def test_matches_jax_native(self, logits_labels):
        logits, labels = logits_labels
        ours = per_sample_nll_pallas(logits, labels)
        ref = per_sample_loss(logits, labels)
        np.testing.assert_allclose(np.asarray(ours), np.asarray(ref), rtol=1e-5)

    def test_vjp_matches_autodiff(self, logits_labels):
        logits, labels = logits_labels

        def f_pallas(lg):
            return jnp.sum(per_sample_nll_pallas(lg, labels) * 0.5)

        def f_ref(lg):
            return jnp.sum(per_sample_loss(lg, labels) * 0.5)

        g_pallas = jax.grad(f_pallas)(logits)
        g_ref = jax.grad(f_ref)(logits)
        np.testing.assert_allclose(np.asarray(g_pallas), np.asarray(g_ref),
                                   rtol=1e-5, atol=1e-6)

    def test_jit_and_bf16_input(self, logits_labels):
        logits, labels = logits_labels
        out = jax.jit(per_sample_nll_pallas)(logits.astype(jnp.bfloat16), labels)
        ref = per_sample_loss(logits.astype(jnp.bfloat16), labels)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-2, atol=1e-2)

    def test_100_classes(self):
        rng = np.random.default_rng(1)
        logits = jnp.asarray(rng.normal(0, 1, (32, 100)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, 100, 32), jnp.int32)
        np.testing.assert_allclose(
            np.asarray(per_sample_nll_pallas(logits, labels)),
            np.asarray(per_sample_loss(logits, labels)), rtol=1e-5,
        )


class TestScoreAndDraw:
    def test_probs_match_pipeline(self):
        losses = jnp.asarray(np.random.default_rng(0).exponential(1.0, 320),
                             jnp.float32)
        ema = jnp.asarray(1.3)
        probs, selected, scaled = score_and_draw_pallas(
            jax.random.key(0), losses, ema, 32, alpha=0.5
        )
        ref_probs = importance_probs(losses, ema, 0.5)
        np.testing.assert_allclose(np.asarray(probs), np.asarray(ref_probs),
                                   rtol=1e-5)
        assert selected.shape == (32,) and scaled.shape == (32,)
        # scaled = p·N for the drawn entries
        np.testing.assert_allclose(
            np.asarray(scaled), np.asarray(ref_probs[selected] * 320), rtol=1e-4
        )

    def test_draw_distribution(self):
        """Inverse-CDF draws must follow the probs empirically."""
        losses = jnp.asarray([0.1, 1.0, 3.0, 0.5], jnp.float32)
        ema = jnp.asarray(0.0)
        counts = np.zeros(4)
        # Few large-batch calls rather than many tiny ones: same statistics,
        # ~20x less interpret-mode overhead on CPU.
        for s in range(10):
            _, selected, _ = score_and_draw_pallas(
                jax.random.key(s), losses, ema, 1000, alpha=0.0
            )
            counts += np.bincount(np.asarray(selected), minlength=4)
        freq = counts / counts.sum()
        expected = np.asarray(importance_probs(losses, ema, 0.0))
        np.testing.assert_allclose(freq, expected, atol=0.02)

    def test_deterministic_per_key(self):
        losses = jnp.linspace(0.1, 2.0, 64)
        a = score_and_draw_pallas(jax.random.key(5), losses, jnp.asarray(1.0), 16)
        b = score_and_draw_pallas(jax.random.key(5), losses, jnp.asarray(1.0), 16)
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))

    def test_extreme_skew_clamps_index(self):
        """u ≈ 1.0 with mass concentrated early must still yield a valid
        index (the N-1 clamp)."""
        losses = jnp.asarray([100.0] + [0.0] * 15, jnp.float32)
        for s in range(20):
            _, selected, _ = score_and_draw_pallas(
                jax.random.key(s), losses, jnp.asarray(0.0), 8, alpha=0.0
            )
            sel = np.asarray(selected)
            assert sel.min() >= 0 and sel.max() < 16


class TestChunkedDrawLargePools:
    """Scores sit lane-dense ([N/128, 128]) and the CDF is two triangular
    matmuls — within a row, then over row totals in 512-row chunks — so a
    whole scoretable shard (tens of thousands of slots) fits VMEM where
    an [N, 1] column would not."""

    @pytest.mark.parametrize("pool", [320, 1024, 2496, 4096])
    def test_probs_and_draw_at_scale(self, pool):
        losses = jnp.asarray(
            np.random.default_rng(7).exponential(1.0, pool), jnp.float32
        )
        ema = jnp.asarray(0.8)
        probs, selected, scaled = score_and_draw_pallas(
            jax.random.key(1), losses, ema, 64, alpha=0.5
        )
        ref_probs = importance_probs(losses, ema, 0.5)
        np.testing.assert_allclose(np.asarray(probs), np.asarray(ref_probs),
                                   rtol=1e-5)
        sel = np.asarray(selected)
        assert ((sel >= 0) & (sel < pool)).all()
        np.testing.assert_allclose(
            np.asarray(scaled), np.asarray(ref_probs)[sel] * pool, rtol=1e-4
        )

    @pytest.mark.parametrize("pool", [5000, 70000])
    def test_draws_match_inverse_cdf_reference(self, pool):
        """Shard-table sizes, one CDF chunk (5,000) and two (70,000 >
        65,536): every draw equals a float64 inverse-CDF on the same
        uniforms — a misplaced row or chunk prefix would shift them all."""
        losses = jnp.asarray(
            np.random.default_rng(5).exponential(1.0, pool), jnp.float32
        )
        ema = jnp.asarray(0.7)
        key = jax.random.key(4)
        probs, selected, _ = score_and_draw_pallas(key, losses, ema, 64)
        cdf = np.cumsum(np.asarray(probs, np.float64))
        u = np.asarray(jax.random.uniform(key, (64,), jnp.float32),
                       np.float64)
        ref = np.minimum(np.searchsorted(cdf, u, side="right"), pool - 1)
        # f32 vs f64 CDFs may disagree only where u lands within rounding
        # of a boundary — at most a neighbouring slot, and rarely.
        sel = np.asarray(selected)
        assert np.abs(sel - ref).max() <= 1
        assert (sel == ref).mean() >= 0.95

    @pytest.mark.parametrize("pool", [625, 2500])
    def test_awkward_pool_sizes(self, pool):
        """Pools that are not a multiple of the (8, 128) tile: the wrapper
        pads to whole tiles and the kernel masks the padding to exactly
        zero probability, so it can never be drawn."""
        losses = jnp.asarray(
            np.random.default_rng(11).exponential(1.0, pool), jnp.float32
        )
        ema = jnp.asarray(1.0)
        probs, selected, scaled = score_and_draw_pallas(
            jax.random.key(3), losses, ema, 128, alpha=0.5
        )
        assert probs.shape == (pool,)
        ref = importance_probs(losses, ema, 0.5)
        np.testing.assert_allclose(np.asarray(probs), np.asarray(ref),
                                   rtol=1e-5)
        sel = np.asarray(selected)
        assert ((sel >= 0) & (sel < pool)).all()
        np.testing.assert_allclose(
            np.asarray(scaled), np.asarray(ref)[sel] * pool, rtol=1e-4
        )

    def test_draw_frequencies_follow_distribution(self):
        """Statistical check at a chunk boundary-heavy size: empirical
        draw frequencies over many draws approximate the probs."""
        pool = 1024
        losses = jnp.asarray(
            np.random.default_rng(9).exponential(1.0, pool), jnp.float32
        )
        ema = jnp.asarray(0.5)
        probs, selected, _ = score_and_draw_pallas(
            jax.random.key(2), losses, ema, 8192, alpha=0.5
        )
        freq = np.bincount(np.asarray(selected), minlength=pool) / 8192
        p = np.asarray(probs)
        # Top-decile mass comparison (per-bin noise at 8k draws is large).
        top = np.argsort(p)[-pool // 10:]
        np.testing.assert_allclose(freq[top].sum(), p[top].sum(), atol=0.03)


_MEAN = np.asarray([0.4914, 0.4822, 0.4465], np.float32)
_STD = np.asarray([0.2470, 0.2435, 0.2616], np.float32)


@pytest.fixture(scope="module")
def raw_uint8():
    rng = np.random.default_rng(3)
    return jnp.asarray(rng.integers(0, 256, (8, 32, 32, 3), dtype=np.uint8))


def _unfused_ingest(key, raw, out_dtype=None):
    out = augment_batch(key, normalize_images(raw, _MEAN, _STD))
    return out if out_dtype is None else out.astype(out_dtype)


class TestHeadNLL:
    """``head_nll_pallas`` (interpret mode, blocks of 32 tokens x 128
    columns) against ``sequence_loss``'s plain form: the token loss to 1e-5
    relative, the hits exactly."""

    BLOCKS = (32, 128)

    @staticmethod
    def _operands(t, d, v, dtype, seed=0, scale=1.0):
        rng = np.random.default_rng(seed)
        hidden = jnp.asarray(rng.standard_normal((t, d)) * scale, dtype)
        head = jnp.asarray(rng.standard_normal((d, v)) * d ** -0.5, dtype)
        labels = rng.integers(0, v, t).astype(np.int32)
        return hidden, head, labels

    def _agree(self, hidden, head, labels, blocks=None):
        labels = jnp.asarray(labels)
        nll, hit = jax.jit(lambda *a: head_nll_pallas(
            *a, blocks or self.BLOCKS))(hidden, head, labels)
        want_nll, want_hit = _token_rows_plain(hidden, head, labels)
        assert nll.shape == hit.shape == labels.shape
        assert nll.dtype == hit.dtype == jnp.float32
        assert np.isfinite(np.asarray(nll)).all()
        np.testing.assert_allclose(nll, want_nll, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(hit, want_hit)
        return np.asarray(nll), np.asarray(hit)

    @pytest.mark.parametrize("t, d, v", [
        (32, 16, 128),      # one block of each
        (64, 16, 256),      # two token blocks, whole vocabulary blocks
        (32, 200, 128),     # a contraction of more than one lane tile
        (32, 16, 200),      # a masked tail of 72 columns
        (96, 48, 333),      # all at once, three vocabulary blocks
        (32, 16, 72),       # a vocabulary smaller than one block
    ], ids=lambda n: str(n))
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                             ids=["bf16", "f32"])
    def test_is_the_plain_form(self, t, d, v, dtype):
        self._agree(*self._operands(t, d, v, dtype, seed=t + d + v))

    @pytest.mark.parametrize("where", ["first_column", "last_column",
                                       "tail_block", "block_edge"])
    def test_finds_the_labels_logit_wherever_it_lies(self, where):
        hidden, head, labels = self._operands(32, 16, 200, jnp.bfloat16, 1)
        labels[:] = {"first_column": 0, "last_column": 199,
                     "tail_block": 150, "block_edge": 128}[where]
        # the label's column made the largest logit of half the rows and
        # the smallest of the others
        head = head.at[:, labels[0]].set(0.0)
        hidden = hidden.at[::2, 0].set(8.0).at[1::2, 0].set(-8.0)
        head = head.at[0, labels[0]].set(4.0)
        _, hit = self._agree(hidden, head, labels)
        assert hit[::2].all() and not hit[1::2].any()

    @pytest.mark.parametrize("columns", [(5, 9), (5, 150), (130, 199),
                                         (0, 128)],
                             ids=["one_block", "across_blocks", "in_the_tail",
                                  "block_firsts"])
    def test_the_lowest_index_wins_an_exact_tie(self, columns):
        """Small whole numbers: every product and sum is exact in any
        order, so the two columns' logits are equal to the bit."""
        rng = np.random.default_rng(2)
        hidden = jnp.asarray(rng.integers(-3, 4, (32, 16)), jnp.float32)
        hidden = hidden.at[:, 0].set(3.0)
        head = np.asarray(rng.integers(-1, 2, (16, 200)), np.float32)
        head[:, columns[1]] = head[:, columns[0]] = 0.0
        head[0, columns[0]] = head[0, columns[1]] = 40.0
        labels = np.full(32, columns[0], np.int32)
        labels[1::2] = columns[1]
        _, hit = self._agree(hidden, jnp.asarray(head), labels)
        assert hit[::2].all() and not hit[1::2].any()

    def test_whole_number_logits_tie_as_argmax_lets_them(self):
        rng = np.random.default_rng(3)
        hidden = jnp.asarray(rng.integers(-2, 3, (64, 16)), jnp.float32)
        head = jnp.asarray(rng.integers(-2, 3, (16, 333)), jnp.float32)
        logits = np.asarray(hidden @ head)
        assert ((logits == logits.max(-1, keepdims=True)).sum(-1) > 1).any()
        self._agree(hidden, head, logits.argmax(-1).astype(np.int32))

    def test_logits_past_the_range_of_exp(self):
        """One row of logits in the hundreds, its maximum in the LAST
        block: a sum of exponentials not rescaled to the running maximum
        overflows (exp(89) is past float32), or underflows to log(0)."""
        hidden, head, labels = self._operands(32, 16, 333, jnp.float32, 4)
        hidden = hidden.at[3].multiply(300.0).at[3, 0].set(300.0)
        head = head.at[0, 300].set(3.0)
        logits = np.asarray(hidden @ head)
        assert logits[3].max() > 800 and logits[3].argmax() == 300
        nll, hit = self._agree(hidden, head, labels)
        assert nll[3] > 100.0

    def test_other_blocks_and_the_shapes_refused(self):
        hidden, head, labels = self._operands(64, 32, 600, jnp.bfloat16, 5)
        self._agree(hidden, head, labels, blocks=(64, 256))   # two groups
        self._agree(hidden, head, labels, blocks=(16, 512))   # four, a tail
        assert head_nll_takes(8192, 2560) and head_nll_takes(8192, 2048)
        assert not head_nll_takes(8192 + 512, 2560)     # half a token block
        assert not head_nll_takes(32, 64)               # the tiny models
        # the contraction's blocks have to fit the kernel's VMEM
        assert head_nll_takes(8192, 4096) and not head_nll_takes(8192, 8192)
        assert not head_nll_takes(8192, 4096, itemsize=4)
        with pytest.raises(ValueError, match="whole"):
            head_nll_pallas(hidden, head, jnp.asarray(labels), (48, 128))
        with pytest.raises(ValueError, match="whole"):
            head_nll_pallas(hidden, head, jnp.asarray(labels), (64, 192))


class TestAugmentNormalize:
    """Fused uint8 ingest vs the unfused normalize→augment chain. Both
    sides are JITTED in every comparison: XLA rewrites the /255 and /std
    divisions (reciprocal-multiply) in compiled programs only, so
    eager-vs-jit differs in the last ulp while jit-vs-jit is bit-exact —
    and jit-vs-jit is the comparison the train step actually makes."""

    @pytest.mark.parametrize("seed", [0, 1, 7])
    def test_bit_identical_f32(self, raw_uint8, seed):
        key = jax.random.key(seed)
        fused = jax.jit(
            lambda k, r: augment_normalize(k, r, _MEAN, _STD)
        )(key, raw_uint8)
        ref = jax.jit(_unfused_ingest)(key, raw_uint8)
        assert fused.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(ref))

    def test_bf16_is_last_op_cast(self, raw_uint8):
        """out_dtype=bfloat16 must equal the f32 result rounded ONCE at
        the end (the scoring path's contract) — not a bf16 compute."""
        key = jax.random.key(2)
        fused = jax.jit(
            lambda k, r: augment_normalize(
                k, r, _MEAN, _STD, out_dtype=jnp.bfloat16)
        )(key, raw_uint8)
        ref = jax.jit(
            lambda k, r: _unfused_ingest(k, r, jnp.bfloat16)
        )(key, raw_uint8)
        assert fused.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(fused, np.float32), np.asarray(ref, np.float32))

    def test_deterministic_per_key(self, raw_uint8):
        key = jax.random.key(11)
        a = augment_normalize(key, raw_uint8, _MEAN, _STD)
        b = augment_normalize(key, raw_uint8, _MEAN, _STD)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _chain_with_draws(raw, off, flip, out_dtype=None):
    """The chain the selection ingest replaced, with the draws handed in:
    normalize, zero-pad 4, crop at ``off``, flip — ``augment_batch``'s own
    ops on ``[N, H, W, C]``, kept here as the reference."""
    from mercury_tpu.data.pipeline import _take_crops

    n, h, w, _ = raw.shape
    padded = jnp.pad(normalize_images(raw, _MEAN, _STD),
                     ((0, 0), (4, 4), (4, 4), (0, 0)))
    out = _take_crops(padded, off[:, 0], off[:, 1], h, w)
    out = jnp.where(flip[:, None, None, None], out[:, :, ::-1, :], out)
    return out if out_dtype is None else out.astype(out_dtype)


def _as_rows(raw, rows):
    """``raw`` as the step hands it to the ingest: ``[N, H, W, C]`` or the
    flat ``[N, H*W*C]`` rows with their ``image_shape``."""
    if rows == "flat":
        return raw.reshape(raw.shape[0], -1), tuple(raw.shape[1:])
    return raw, None


class TestSelectIngest:
    """The one-pass uint8 ingest (crop and flip as exact selection on the
    raw bytes, normalize last) against the normalize → pad → crop → flip
    chain it replaced: bitwise, jit against jit, at f32 and through the
    bf16 cast."""

    @pytest.mark.parametrize("rows", ["nhwc", "flat"])
    @pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("seed", [0, 1, 7, 2**31 + 11])
    def test_seeded_keys(self, raw_uint8, seed, out_dtype, rows):
        key = jax.random.key(seed)
        x, shape = _as_rows(raw_uint8, rows)
        new = jax.jit(lambda k, r: augment_normalize(
            k, r, _MEAN, _STD, out_dtype=out_dtype, image_shape=shape)
        )(key, x)
        ref = jax.jit(lambda k, r: _unfused_ingest(k, r, out_dtype)
                      )(key, raw_uint8)
        assert new.dtype == out_dtype and new.shape == raw_uint8.shape
        np.testing.assert_array_equal(
            np.asarray(new, np.float32), np.asarray(ref, np.float32))

    @pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("flip", [False, True])
    @pytest.mark.parametrize("ox", [0, 8])
    @pytest.mark.parametrize("oy", [0, 8])
    def test_forced_corner_draws(self, raw_uint8, oy, ox, flip, out_dtype):
        """The four corners of the padded image, flipped and not: rows and
        columns of exact zeros on two sides, the image's own corner pixel
        at the opposite one."""
        n = raw_uint8.shape[0]
        off = jnp.tile(jnp.asarray([[oy, ox]], jnp.int32), (n, 1))
        flips = jnp.full((n,), flip)
        new = jax.jit(lambda r, o, f: select_crop_flip(
            r, o, f, _MEAN, _STD, out_dtype=out_dtype))(raw_uint8, off, flips)
        ref = jax.jit(lambda r, o, f: _chain_with_draws(r, o, f, out_dtype)
                      )(raw_uint8, off, flips)
        np.testing.assert_array_equal(
            np.asarray(new, np.float32), np.asarray(ref, np.float32))
        got = np.asarray(new, np.float32)
        pad_rows = slice(0, 4) if oy == 0 else slice(28, 32)
        assert (got[:, pad_rows] == 0.0).all()
        assert not (got[:, 4:28, 4:28] == 0.0).all()

    def test_each_image_its_own_draw(self, raw_uint8):
        """Every offset pair and both flips in one batch: no draw leaks
        from one image into another."""
        n = raw_uint8.shape[0]
        off = jnp.asarray([[i % 9, (3 * i + 2) % 9] for i in range(n)],
                          jnp.int32)
        flips = jnp.arange(n) % 2 == 0
        new = jax.jit(lambda r: select_crop_flip(
            r.reshape(n, -1), off, flips, _MEAN, _STD,
            image_shape=(32, 32, 3)))(raw_uint8)
        ref = jax.jit(lambda r: _chain_with_draws(r, off, flips))(raw_uint8)
        np.testing.assert_array_equal(np.asarray(new), np.asarray(ref))

    def test_draws_replay_augment_batch(self, raw_uint8):
        """``crop_flip_draws`` is the seam: the same key gives the draws
        ``augment_batch`` makes."""
        key = jax.random.key(5)
        off, flips = crop_flip_draws(key, raw_uint8.shape[0])
        ref = jax.jit(_unfused_ingest)(key, raw_uint8)
        via = jax.jit(lambda r: _chain_with_draws(r, off, flips))(raw_uint8)
        np.testing.assert_array_equal(np.asarray(via), np.asarray(ref))

    @pytest.mark.parametrize("bad", ["float", "flat_without_shape"])
    def test_rejects_what_it_cannot_select(self, raw_uint8, bad):
        off, flips = crop_flip_draws(jax.random.key(0), raw_uint8.shape[0])
        x = (raw_uint8.astype(jnp.float32) if bad == "float"
             else raw_uint8.reshape(raw_uint8.shape[0], -1))
        with pytest.raises(ValueError, match="select_crop_flip"):
            select_crop_flip(x, off, flips, _MEAN, _STD)
