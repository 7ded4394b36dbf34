"""The causal decoder, its attention forms, its routed experts and the
next-token seam, at a small size on the CPU: d_model 64, 4 query heads on 1
key/value head of 16, window 8 at T 32, 16 experts top-3 of which 4 are
held, vocabulary 96 (``chip_smoke.register_tiny_lm``: the size its token
phase runs). The plain reference's side of the same model is
``tests/perfbench/test_smallthinker.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_smoke import register_tiny_latent_lm, register_tiny_lm
from mercury_tpu import TrainConfig
from mercury_tpu.data.tokens import zipf_tokens
from mercury_tpu.models import LM_WIDTHS, create_model
from mercury_tpu.models import decoder, moe
from mercury_tpu.models.moe import route_top_k, routed_experts
from mercury_tpu.sampling.importance import (
    sequence_loss,
    sequence_rows,
    token_logits,
)
from mercury_tpu.train.stages import row_fns

TINY = register_tiny_lm()
WIDTHS = LM_WIDTHS[TINY]
#: The decoder's other mixer and routing rule at the CPU's size: latent
#: attention (4 heads of 128 + 64 against 128 over a latent of 32), a
#: sigmoid router with a selection bias over 16 SwiGLU experts top-3, a
#: shared expert of 64, one leading dense layer of 96.
LATENT = register_tiny_latent_lm()
VOCAB, T = 96, 32


def _qkv(t=T, kv=2, groups=2, hd=16, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((kv, groups, t, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((kv, t, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((kv, t, hd)), jnp.float32)
    return q * hd ** -0.5, k, v


# --------------------------------------------------------------- attention
@pytest.mark.parametrize("window", [None, 8, 5])
def test_blockwise_attention_is_the_dense_one(window):
    """(v) A block of queries at a time over the keys its mask can reach
    gives what the [T, T] form gives, forward and gradient; float32 on both
    sides, so only the order of a row's sum differs (1e-5)."""
    q, k, v = _qkv()

    def loss(fn, *a):
        return jnp.sum(jnp.square(fn(*a)))

    dense = jax.value_and_grad(
        lambda *a: loss(decoder.dense_attention, *a, window), (0, 1, 2))
    block = jax.value_and_grad(
        lambda *a: loss(decoder.blockwise_attention, *a, window, 8),
        (0, 1, 2))
    (a, ga), (b, gb) = dense(q, k, v), block(q, k, v)
    np.testing.assert_allclose(a, b, rtol=1e-5)
    for x, y in zip(ga, gb):
        np.testing.assert_allclose(x, y, atol=1e-5)


def test_a_window_masks_exactly_the_keys_behind_it():
    """(iv) At T <= window a windowed layer is full attention; past it the
    two differ, and the windowed output of query i is full attention over
    keys i - window + 1 .. i alone."""
    q, k, v = _qkv()
    full = decoder.dense_attention(q, k, v, None)
    np.testing.assert_array_equal(
        decoder.dense_attention(q, k, v, T), full)
    windowed = decoder.blockwise_attention(q, k, v, 8, 8)
    np.testing.assert_allclose(windowed[:, :, :8], full[:, :, :8], rtol=1e-5,
                               atol=1e-6)
    assert float(jnp.abs(windowed[:, :, 8:] - full[:, :, 8:]).max()) > 1e-3
    i = 20
    alone = decoder.dense_attention(q[:, :, i - 7:i + 1], k[:, i - 7:i + 1],
                                    v[:, i - 7:i + 1], None)[:, :, -1]
    np.testing.assert_allclose(windowed[:, :, i], alone, rtol=1e-5, atol=1e-6)


def test_splash_kernel_is_the_blockwise_form():
    """The TPU path's kernel (interpreted here) against the XLA form at the
    kernel's own shapes (head size 128, T 256, 2 query heads a key/value
    head), causal and windowed, forward and gradient. bfloat16 products in
    the kernel's inner loop are not in play at float32 inputs; the
    tolerance is for its online softmax (1e-4)."""
    q, k, v = _qkv(t=256, kv=1, groups=2, hd=128)
    assert decoder.splash_takes(256, 128) and not decoder.splash_takes(T, 16)
    for window in (None, 128):
        want, gw = jax.value_and_grad(lambda q: jnp.sum(jnp.square(
            decoder.blockwise_attention(q, k, v, window, 128))))(q)
        got, gg = jax.value_and_grad(lambda q: jnp.sum(jnp.square(
            decoder.splash_attention(q, k, v, window))))(q)
        np.testing.assert_allclose(got, want, rtol=1e-4)
        np.testing.assert_allclose(gg, gw, atol=1e-4)


def test_rope_moves_with_the_positions_and_nope_does_not():
    """(iv) Scores of rotated queries and keys depend on the distance of
    their positions alone: a shift of both leaves them; unrotated ones have
    no position at all. So a NoPE layer is invariant to a shift of positions
    and a RoPE layer's q and k are not."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((T, 2, 16)), jnp.float32)
    a, b = (decoder.rotate_half(x, 10_000.0, offset) for offset in (0, 5))
    assert float(jnp.abs(a - b).max()) > 1e-2          # not invariant
    scores = [jnp.einsum("qhd,khd->hqk", r, r) for r in (a, b)]
    np.testing.assert_allclose(*scores, atol=1e-4)     # distances are
    np.testing.assert_array_equal(decoder.rotate_half(x, 10_000.0)[0], x[0])


# ----------------------------------------------------------------- experts
def _experts(held, seed=0, d=64, f=32):
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.1, jnp.float32)  # noqa: E731
    return mk(held, d, f), mk(held, d, f), mk(held, f, d)


def test_the_shares_of_four_holders_add_up_to_the_uncut_layer():
    """(iii) The layer told to hold experts 0-3, 4-7, 8-11, 12-15 in turn
    gives four partial outputs whose sum is the uncut layer's, which is the
    dense sum over the chosen experts of weight x expert."""
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((T, 64)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((T, 16)), jnp.float32)
    gate, up, down = _experts(16)
    whole, (share, *_) = routed_experts(h, r, gate, up, down, 3, 0)
    assert float(share) == 1.0
    parts = 0.0
    for first in range(0, 16, 4):
        held = slice(first, first + 4)
        y, (share, *_) = routed_experts(h, r, gate[held], up[held],
                                       down[held], 3, first)
        parts = parts + y
        assert 0.0 < float(share) < 1.0
    np.testing.assert_allclose(parts, whole, atol=1e-5)
    logits, chosen = jax.lax.top_k(r, 3)
    w = jax.nn.softmax(logits, -1)
    dense = sum(
        jnp.sum(jnp.where(chosen == e, w, 0.0), -1)[:, None]
        * ((jax.nn.relu(h @ gate[e]) * (h @ up[e])) @ down[e])
        for e in range(16))
    np.testing.assert_allclose(whole, dense, atol=1e-5)


def test_no_token_is_dropped_when_one_held_expert_takes_them_all():
    """(vi) Every token's first choice is held expert 1: its group holds all
    T pairs, no capacity cuts any, and the output is that expert's, weighted,
    for every token. The gradient reaches the expert from every token."""
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.standard_normal((T, 64)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((T, 16)), jnp.float32)
    r = r.at[:, 1].set(10.0).at[:, :4].add(jnp.asarray([0, 0, -20., -20.]))
    gate, up, down = _experts(4)
    weights, _, _, sizes, is_held = route_top_k(r, 3, 0, 4)
    assert int(sizes[1]) == T and int(sizes[2]) == int(sizes[3]) == 0
    y, (_, busiest, _) = routed_experts(h, r, gate, up, down, 3, 0)
    # the held choices are expert 1 and, for some tokens, expert 0
    _, chosen = jax.lax.top_k(r, 3)
    per_choice = jnp.stack([jnp.stack(
        [(jax.nn.relu(h @ gate[e]) * (h @ up[e])) @ down[e]
         for e in range(4)], 0)[jnp.clip(chosen[:, c], 0, 3),
                                jnp.arange(T)] for c in range(3)], 1)
    want = jnp.sum(jnp.where(is_held, weights, 0.0)[..., None] * per_choice,
                   1)
    np.testing.assert_allclose(y, want, atol=1e-5)
    assert float(busiest) >= 2.0
    g = jax.grad(lambda gate: jnp.sum(routed_experts(
        h, r, gate, up, down, 3, 0)[0]))(gate)
    assert float(jnp.abs(g[1]).max()) > 0 and float(jnp.abs(g[2]).max()) == 0


# The bound on the rows that follow the sort: 2 of 16 experts held at top-3
# and T = 256 is a bound of 2 x 768 x 2 / 16 = 192 -> 256 rows under the 768
# pairs (at the file's T = 32 the bound is all 96 and the cases above trace
# the uncut program).
LONG_T, HELD, FIRST = 256, 2, 4


def _a_holder_of_two(seed, forced=False):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.standard_normal((LONG_T, 64)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((LONG_T, 16)), jnp.float32)
    if forced:      # every token's first two choices are the held experts
        r = r.at[:, FIRST:FIRST + HELD].add(10.0)
    return (h, r) + _experts(HELD, seed)


def _dense_over_held(h, r, gate, up, down):
    logits, chosen = jax.lax.top_k(r, 3)
    w = jax.nn.softmax(logits, -1)
    return sum(
        jnp.sum(jnp.where(chosen == FIRST + e, w, 0.0), -1)[:, None]
        * ((jax.nn.relu(h @ gate[e]) * (h @ up[e])) @ down[e])
        for e in range(HELD))


def _value_and_grads(fn, args):
    def loss(*a):
        y, load = fn(*a, 3, FIRST)
        return jnp.sum(jnp.square(y)), load
    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(
        *args)


@pytest.mark.parametrize("forced, bounded", [(False, 1.0), (True, 0.0)],
                         ids=["under-the-bound", "over-the-bound"])
def test_the_bounded_rows_and_the_uncut_ones_are_one_sum(monkeypatch, forced,
                                                         bounded):
    """Held pairs under the bound (a share near 2/16 against 1/3 of the
    pairs) take the bounded rows, held pairs over it (every token's first
    two choices forced onto the two held experts: 2/3) all ``k * T``: both
    give the dense sum over the chosen and held experts, no pair dropped,
    and the values and the gradients to ``h``, the router's logits and the
    three weights that the uncut program gives on the same inputs."""
    args = _a_holder_of_two(7, forced)
    assert moe.pair_bound(3 * LONG_T, HELD, 16) == 256
    (got, (share, _, fits)), grads = _value_and_grads(routed_experts, args)
    assert float(fits) == bounded
    assert (float(share) * 3 * LONG_T <= 256) == bool(bounded)
    if forced:
        assert float(share) == pytest.approx(2 / 3)
    y, _ = routed_experts(*args, 3, FIRST)
    np.testing.assert_allclose(y, _dense_over_held(*args), atol=1e-5)
    # the uncut program: a factor under which the bound is all the pairs
    monkeypatch.setattr(moe, "ROWS_OVER_UNIFORM", 8)
    assert moe.pair_bound(3 * LONG_T, HELD, 16) == 3 * LONG_T
    (want, (_, _, fits)), uncut = _value_and_grads(routed_experts, args)
    assert float(fits) == 1.0
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name, a, b in zip(("h", "router", "gate", "up", "down"), grads,
                          uncut):
        assert float(jnp.abs(b).max()) > 0, name
        np.testing.assert_allclose(a, b, atol=1e-5, err_msg=name)


def _primitives(jaxpr, arm=None):
    """Every equation of a jaxpr and of the jaxprs inside it; of a ``cond``
    only branch ``arm`` where one is named."""
    for eqn in jaxpr.eqns:
        yield eqn
        inner = []
        for name, value in eqn.params.items():
            if eqn.primitive.name == "cond" and name == "branches" \
                    and arm is not None:
                value = [value[arm]]
            for v in value if isinstance(value, (tuple, list)) else [value]:
                v = getattr(v, "jaxpr", v)
                if hasattr(v, "eqns"):
                    inner.append(v)
        for sub in inner:
            yield from _primitives(sub, arm)


def test_a_layer_held_whole_traces_no_cond():
    """``held == E``: the bound is all the pairs, the program is the uncut
    one and holds no ``cond``, forward or differentiated; a holder of a
    share under the bound has one forward and one more backward."""
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((LONG_T, 64)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((LONG_T, 16)), jnp.float32)

    def conds(experts, first):
        def loss(h, r, *w):
            return jnp.sum(routed_experts(h, r, *w, 3, first)[0])
        fwd = jax.make_jaxpr(loss)(h, r, *experts).jaxpr
        bwd = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
            h, r, *experts).jaxpr
        return [sum(e.primitive.name == "cond" for e in _primitives(j))
                for j in (fwd, bwd)]

    assert conds(_experts(16), 0) == [0, 0]
    assert conds(_experts(HELD), FIRST) == [1, 2]


def test_the_bounded_arm_holds_no_array_of_all_the_pairs_but_the_return():
    """A walk over the jaxpr of the bounded arm, forward and ``jax.grad``
    (top level and the ``cond``s' bounded branches; T = 512, so that the
    bound, 384 rows of the 1,536 pairs, is neither T nor ``k * T``). The
    grouped products see ``C`` rows, nothing of ``F`` columns has ``k * T``
    rows, and what has ``k * T`` rows of ``D`` columns is the return to the
    tokens alone: the gather of every pair's row out of the experts' ``[C,
    D]`` output with its select and weighting and, backward, the same
    gather of the rows' gradients out of ``[C, D]`` on their way to ``h``.
    No gather reads ``k * T`` rows of ``h`` itself, and the backward pass
    keeps no residual of the arm not taken (``lax.cond``'s own derivative
    would)."""
    t, k, d, f = 512, 3, 64, 32
    rng = np.random.default_rng(11)
    h = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((t, 16)), jnp.float32)
    experts = _experts(HELD, 11)
    bound = moe.pair_bound(k * t, HELD, 16)
    assert bound == 384

    def loss(*a):
        return jnp.sum(routed_experts(*a, k, FIRST)[0])

    def rows(shape):
        return int(np.prod(shape[:-1])) if len(shape) >= 2 else 0

    for fn, gathers in ((loss, 1),
                        (jax.grad(loss, argnums=(0, 1, 2, 3, 4)), 3)):
        jaxpr = jax.make_jaxpr(fn)(h, r, *experts).jaxpr
        seen = 0
        for eqn in _primitives(jaxpr, arm=1):
            ins = [v.aval.shape for v in eqn.invars if hasattr(v, "aval")]
            outs = [v.aval.shape for v in eqn.outvars]
            for shape in ins + outs:
                assert not (rows(shape) == k * t and shape[-1] == f), eqn
            if eqn.primitive.name.startswith("ragged_dot"):
                assert all(k * t not in shape for shape in ins + outs), eqn
            if eqn.primitive.name == "gather" and outs[0] == (k * t, d):
                assert ins[0] == (bound, d), eqn
                seen += 1
        assert seen == gathers


# ---------------------------------------------------------------- the seam
def test_the_row_at_a_time_head_and_loss_are_the_whole_product():
    """(v) ``sequence_loss`` a row at a time is log-softmax cross-entropy
    over the whole [N, T, V] logits, mean over T; the seam of token rows
    (``stages.row_fns(token_rows=True)``) reduces a model's outputs to
    it, and reads loss, score and hits off the reduction."""
    rng = np.random.default_rng(3)
    hidden = jnp.asarray(rng.standard_normal((3, T, 64)), jnp.float32)
    head = jnp.asarray(rng.standard_normal((64, VOCAB)) * 0.1, jnp.float32)
    labels = jnp.asarray(rng.integers(0, VOCAB, (3, T)), jnp.int32)
    rows = jax.lax.map(lambda a: sequence_loss(a[0], head, a[1]),
                       (hidden, labels))
    seam = row_fns(token_rows=True)
    assert seam.reduce.func is sequence_rows
    assert seam.reduce.keywords == {"use_kernel": False}
    assert row_fns(token_rows=True, use_pallas=True).reduce.keywords == {
        "use_kernel": True}
    np.testing.assert_array_equal(seam.reduce((hidden, head), labels), rows)
    logits = hidden @ head
    np.testing.assert_array_equal(token_logits((hidden, head)), logits)
    logp = jax.nn.log_softmax(logits, -1)
    want = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0].mean(-1)
    np.testing.assert_allclose(seam.loss(rows, labels), want, rtol=1e-6)
    np.testing.assert_array_equal(seam.score(rows, labels),
                                  seam.loss(rows, labels))
    np.testing.assert_allclose(
        seam.hits(rows, labels),
        (jnp.argmax(logits, -1) == labels).mean(-1), rtol=1e-6)
    # one class label a row: the logits are carried whole
    plain = row_fns()
    assert plain.reduce(logits, None) is logits
    np.testing.assert_array_equal(
        plain.hits(logits[:, 0], labels[:, 0]),
        jnp.argmax(logits[:, 0], -1) == labels[:, 0])


def test_the_models_rows_are_its_whole_logits_reduced():
    model = create_model(TINY, num_classes=VOCAB,
                         compute_dtype="float32", cut=(2, 4, 4))
    (x, y), _ = zipf_tokens(VOCAB, T, train_size=4, test_size=0, seed=1)
    variables = model.init(jax.random.key(0), x[:1], train=False)
    assert sorted(variables["params"]) == ["embed", "final_norm", "head",
                                           "layer0", "layer1"]
    assert variables["params"]["layer0"]["gate"].shape == (4, 64, 32)
    assert variables["params"]["layer0"]["router"].shape == (64, 16)
    hidden, head = model.apply(variables, x, train=False)
    assert hidden.shape == (4, T, 64) and head.shape == (64, VOCAB)
    logits = token_logits((hidden, head))
    assert logits.shape == (4, T, VOCAB) and logits.dtype == jnp.float32
    outputs, written = model.apply({"params": variables["params"]}, x,
                                   train=True, mutable=[decoder.MOE_LOAD])
    rows = sequence_rows(outputs, jnp.asarray(y))
    logp = jax.nn.log_softmax(logits, -1)
    want = -jnp.take_along_axis(logp, y[..., None], -1)[..., 0].mean(-1)
    np.testing.assert_allclose(rows[:, 0], want, rtol=1e-5)
    load = written[decoder.MOE_LOAD]
    assert 0.0 < float(load["held_pair_share"][0]) < 1.0
    assert float(load["load_max_over_mean"][0]) >= 1.0
    with pytest.raises(ValueError, match="cut"):
        create_model(TINY, num_classes=VOCAB,
                     cut=(2, 14, 4)).init(jax.random.key(0), x[:1])


def test_zipf_tokens_are_seeded_shifted_and_half_patterned():
    (x, y), (xt, yt) = zipf_tokens(VOCAB, T, train_size=6, test_size=2,
                                   seed=5, pattern=4)
    again = zipf_tokens(VOCAB, T, train_size=6, test_size=2, seed=5,
                        pattern=4)
    np.testing.assert_array_equal(x, again[0][0])
    assert x.shape == y.shape == (6, T) and xt.shape == (2, T)
    assert x.dtype == np.int32 and 0 <= x.min() and x.max() < VOCAB
    np.testing.assert_array_equal(x[:, 1:], y[:, :-1])       # shifted by one
    np.testing.assert_array_equal(x[1, 4:], x[1, :-4])       # odd: pattern
    assert (x[0, 4:] != x[0, :-4]).any()                      # even: iid
    other = zipf_tokens(VOCAB, T, train_size=6, test_size=2, seed=6)[0][0]
    assert (other != x).any()


# ------------------------------------------------------------- Trainer.fit
def _config(**kw):
    fields = dict(
        model=TINY, dataset="tokens_zipf",
        model_cut=(4, 0, 4), num_classes=VOCAB, seq_len=T, world_size=1,
        batch_size=2, presample_batches=3, augmentation="none",
        compute_dtype="float32", pipelined_scoring=True, steps_per_epoch=1,
        num_epochs=1000, log_every=4, eval_every=0, checkpoint_every=0,
        heartbeat_every=0, trace=True)
    fields.update(kw)
    return TrainConfig(**fields)


@pytest.mark.parametrize("world", [1, 2])
def test_fit_on_the_token_dataset(world):
    """(vii) A few steps through ``Trainer.fit()`` under pipelined scoring:
    the loss is finite and falls from ln(96), ``state.pending`` holds
    integer rows and per-token labels as the replay reads them,
    ``evaluate()`` and ``predict()`` are shaped as the harness assumes, the
    routing reaches the log record and the tracer."""
    from mercury_tpu.train import Trainer

    records = []
    with Trainer(_config(world_size=world)) as t:
        assert t._ingest_path == "tokens" and t._token_rows
        t.logger.add_observer(lambda rec: records.append(dict(rec)))
        out = t.fit(num_epochs=9)
        assert set(out) == {"test/eval_loss", "test/eval_acc"}
        assert np.isfinite(out["test/eval_loss"])
        assert out["test/eval_loss"] < np.log(VOCAB)
        pending = t.state.pending
        assert pending.images.shape == (world, 2, T)
        assert pending.images.dtype == jnp.int32
        assert pending.labels.shape == (world, 2, T)
        assert pending.scaled_probs.shape == (world, 2)
        ds = t.dataset
        assert ds.x_train.shape == (512, T) and ds.y_test.shape == (8, T)
        logits = t.predict(np.asarray(ds.x_test)[:2])
        assert logits.shape == (2, T, VOCAB)
        both = t.evaluate(include_train=True)
        assert "train/eval_loss" in both
        assert both["test/eval_loss"] == out["test/eval_loss"]
        with pytest.raises(ValueError, match="per-token"):
            t.per_class_accuracy()
        events = t.tracer.snapshot()
    assert len(records) == 2 and np.isfinite(records[-1]["train/loss"])
    assert 0.0 < records[-1]["moe/held_pair_share"] <= 1.0
    assert 0.0 <= records[-1]["moe/bounded_share"] <= 1.0
    loads = [e for e in events if e["name"] == "trainer/moe_load"]
    assert len(loads) == 2
    for name in ("held_pair_share", "bounded_share"):
        assert loads[-1]["args"][name] == pytest.approx(
            records[-1][f"moe/{name}"])
    units = [e for e in events if e["name"] == "trainer/bn_moment_units"]
    assert units and units[-1]["args"]["units"] == 0
    # off the TPU the head's kernel is not asked for: the pool's six rows
    heads = [e for e in events if e["name"] == "trainer/head_kernel_rows"]
    assert len(heads) == 1
    assert (heads[0]["args"]["rows"], heads[0]["args"]["plain_rows"]) == (0, 6)
    # nor the one-pass operands: four layers' q, k, v by the plain forms
    assert _operand_sites(events) == [(0, 12)]


@pytest.mark.parametrize("fields, match", [
    (dict(model="smallcnn"), "per-token logits"),
    (dict(dataset="synthetic", num_classes=None), "token ids"),
    (dict(sampler="scoretable", pipelined_scoring=False), "sampler='pool'"),
    (dict(importance_score="grad_norm"), "sampler='pool'"),
    (dict(label_smoothing=0.1), "sampler='pool'"),
])
def test_what_cannot_take_token_rows_is_refused(fields, match):
    from mercury_tpu.train import Trainer

    with pytest.raises(ValueError, match=match):
        Trainer(_config(**fields))


def test_uniform_sampling_takes_token_rows_too():
    from mercury_tpu.train import Trainer

    with Trainer(_config(use_importance_sampling=False,
                         pipelined_scoring=False, trace=False)) as t:
        out = t.fit(num_epochs=3)
    assert np.isfinite(out["test/eval_loss"])


# ------------------------------------- the head's kernel behind the seam
#: Blocks the tiny models' rows (T 32) are whole blocks of; the vocabulary of
#: 96 is one masked block.
SMALL_BLOCKS = (16, 128)


@pytest.fixture
def small_blocks(monkeypatch):
    """The blocks are read as a row's loss is traced, and jax keeps that
    trace by the function and its shapes: forget it on the way in and out."""
    from mercury_tpu.ops import mercury_kernels

    monkeypatch.setattr(mercury_kernels, "HEAD_BLOCKS", SMALL_BLOCKS)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _scoped(jaxpr, prefix=""):
    """``(scopes, equation)`` of a jaxpr and of the jaxprs inside it, the
    scopes those of every enclosing equation and its own."""
    for eqn in jaxpr.eqns:
        path = f"{prefix}/{eqn.source_info.name_stack}"
        yield path, eqn
        for value in eqn.params.values():
            for v in value if isinstance(value, (tuple, list)) else [value]:
                v = getattr(v, "jaxpr", v)
                if hasattr(v, "eqns"):
                    yield from _scoped(v, path)


def _kernels(jaxpr, name):
    return [path for path, eqn in _scoped(jaxpr)
            if eqn.primitive.name == "pallas_call"
            and eqn.params["name"] == name]


def _head_kernels(jaxpr):
    return _kernels(jaxpr, "mercury_head_nll")


def _operand_sites(events):
    """``(sites, plain_sites)`` of each ``trainer/rope_kernel_sites``."""
    return [(e["args"]["sites"], e["args"]["plain_sites"]) for e in events
            if e["name"] == "trainer/rope_kernel_sites"]


def _whole_logits(jaxpr, rows=T, vocab=VOCAB):
    """Scopes of the head's products that write one row's ``[T, V]``
    logits."""
    return [path for path, eqn in _scoped(jaxpr)
            if eqn.primitive.name == "dot_general"
            and "mercury_lm_head" in path
            and eqn.outvars[0].aval.shape == (rows, vocab)]


def _rows_operands(dtype, seed=7, n=3, d=64):
    rng = np.random.default_rng(seed)
    hidden = jnp.asarray(rng.standard_normal((n, T, d)), dtype)
    head = jnp.asarray(rng.standard_normal((d, VOCAB)) * 0.1, dtype)
    return hidden, head, jnp.asarray(rng.integers(0, VOCAB, (n, T)), jnp.int32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_kernels_rows_are_the_plain_rows_and_their_gradient(small_blocks,
                                                                dtype):
    """With the kernel on, a pass that nothing differentiates reads loss
    and hits off the kernel (to rounding, and exactly); ``jax.grad`` through
    ``sequence_rows`` is the plain form's gradient to the bit: the
    ``custom_vjp`` rule IS the plain form and its transpose, the same
    operations in the same order under the same ``jax.checkpoint``."""
    hidden, head, labels = _rows_operands(dtype)
    plain = sequence_rows((hidden, head), labels)
    rows = jax.jit(lambda h, w: sequence_rows((h, w), labels,
                                              use_kernel=True))(hidden, head)
    np.testing.assert_allclose(rows[:, 0], plain[:, 0], rtol=1e-5)
    np.testing.assert_array_equal(rows[:, 1], plain[:, 1])
    forward = jax.make_jaxpr(lambda h, w: sequence_rows(
        (h, w), labels, use_kernel=True))(hidden, head).jaxpr
    assert len(_head_kernels(forward)) == 1 and not _whole_logits(forward)
    assert "mercury_lm_head" in _head_kernels(forward)[0]

    def loss(use_kernel):
        return lambda h, w: jnp.sum(
            sequence_rows((h, w), labels, use_kernel=use_kernel)[:, 0]
            * jnp.arange(1.0, 4.0))

    grads = jax.grad(loss(True), argnums=(0, 1))
    for got, want in zip(grads(hidden, head),
                         jax.grad(loss(False), argnums=(0, 1))(hidden, head)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got.astype(jnp.float32),
                                      want.astype(jnp.float32))
    backward = jax.make_jaxpr(grads)(hidden, head).jaxpr
    assert not _head_kernels(backward)
    assert len(_whole_logits(backward)) == 2      # forward, and recomputed


@pytest.mark.parametrize("t, d, takes", [(T, 64, True), (T + 8, 64, False),
                                         (T, 2 ** 19, False)])
def test_a_shape_the_kernel_refuses_runs_the_plain_form(small_blocks, t, d,
                                                        takes):
    from mercury_tpu.sampling.importance import head_takes_kernel

    hidden = jax.ShapeDtypeStruct((2, t, d), jnp.float32)
    head = jax.ShapeDtypeStruct((d, VOCAB), jnp.float32)
    labels = jnp.asarray(np.random.default_rng(t).integers(
        0, VOCAB, (2, t)), jnp.int32)
    assert head_takes_kernel(hidden, True) is takes
    assert head_takes_kernel(hidden, False) is False
    jaxpr = jax.make_jaxpr(lambda h, w: sequence_rows(
        (h, w), labels, use_kernel=True))(hidden, head).jaxpr
    assert len(_head_kernels(jaxpr)) == int(takes)
    assert len(_whole_logits(jaxpr, t)) == int(not takes)
    if not takes and d == 64:
        rng = np.random.default_rng(t + d)
        hidden = jnp.asarray(rng.standard_normal((2, t, d)), jnp.float32)
        head = jnp.asarray(rng.standard_normal((d, VOCAB)) * 0.1, jnp.float32)
        np.testing.assert_array_equal(
            sequence_rows((hidden, head), labels, use_kernel=True),
            sequence_rows((hidden, head), labels))


def test_the_default_blocks_refuse_the_tiny_rows_and_the_trace_says_so():
    """``use_pallas`` at T 32 with the chip's blocks: every head in the plain
    form, and ``trace_facts`` / the instant count the pool's rows as such."""
    from mercury_tpu.train import Trainer

    with Trainer(_config(use_pallas=True)) as t:
        t.fit(num_epochs=1)
        assert t._trace_facts["head_kernel_rows"] == 0
        assert t._trace_facts["head_plain_rows"] == 6
        events = t.tracer.snapshot()
        step = jax.make_jaxpr(t.train_step)(
            t.state, t._step_x, t._step_y, t.dataset.shard_indices).jaxpr
    assert not _head_kernels(step)
    heads = [e for e in events if e["name"] == "trainer/head_kernel_rows"]
    assert (heads[-1]["args"]["rows"], heads[-1]["args"]["plain_rows"]) == (0, 6)
    # heads of 16 are no whole lanes: the operands' kernel refuses them too
    assert not _kernels(step, "mercury_rope_heads")
    assert _operand_sites(events) == [(0, 12)]


@pytest.mark.parametrize("model", [TINY, LATENT], ids=["softmax", "latent"])
def test_the_step_runs_the_kernel_where_nothing_differentiates(small_blocks,
                                                               model):
    """A token step traced with ``use_pallas``: the scoring pass's head is
    the kernel under ``mercury_scoring`` > ``mercury_lm_head`` and writes no
    ``[T, V]`` logits; the train pass holds the plain product (forward and
    recomputed backward) and no kernel; ``evaluate()``'s pass is the kernel
    too; the pool's six rows are counted."""
    from mercury_tpu.train import Trainer

    cut = (4, 0, 4) if model == TINY else (2, 0, 4, 0, 2)
    with Trainer(_config(model=model, model_cut=cut, use_pallas=True)) as t:
        step = jax.make_jaxpr(t.train_step)(
            t.state, t._step_x, t._step_y, t.dataset.shard_indices).jaxpr
        assert t._trace_facts["head_kernel_rows"] == 6
        assert t._trace_facts["head_plain_rows"] == 0
        evaluate = jax.make_jaxpr(t.eval_epoch)(
            t.state.params, t.state.batch_stats, *t._eval_arrays(False)).jaxpr
        out = t.fit(num_epochs=2)
        events = t.tracer.snapshot()
    kernels = _head_kernels(step)     # the pool's scoring, and its priming
    assert kernels and all(
        "mercury_train" not in path
        and path.index("mercury_scoring") < path.index("mercury_lm_head")
        for path in kernels)
    logits = _whole_logits(step)
    assert len(logits) == 2 and all(
        "mercury_train" in path and "mercury_scoring" not in path
        for path in logits)
    assert len(_head_kernels(evaluate)) == 1 and not _whole_logits(evaluate)
    assert "mercury_lm_head" in _head_kernels(evaluate)[0]
    assert np.isfinite(out["test/eval_loss"])
    heads = [e for e in events if e["name"] == "trainer/head_kernel_rows"]
    assert (heads[-1]["args"]["rows"], heads[-1]["args"]["plain_rows"]) == (6, 0)
    # neither tiny model has heads the operands' kernel takes (16; 128 + 64
    # rotated in part): three plain sites a layer
    assert not _kernels(step, "mercury_rope_heads")
    assert _operand_sites(events) == [(0, 3 * cut[0])]


def test_the_kernels_eval_is_the_plain_eval(small_blocks):
    """``evaluate()`` with the kernel against the same parameters read
    through whole logits: the loss to rounding, the hits to the digit."""
    from mercury_tpu.train import Trainer

    with Trainer(_config(use_pallas=True)) as kernel, \
            Trainer(_config(use_pallas=False)) as plain:
        plain.state = plain.state.replace(params=kernel.state.params)
        got, want = kernel.evaluate(), plain.evaluate()
    assert got["test/eval_loss"] == pytest.approx(want["test/eval_loss"],
                                                  rel=1e-5)
    assert got["test/eval_acc"] == want["test/eval_acc"]


# ------------------------- the attention's operands in one pass (PR 47)
#: The tiny model with heads the operands' kernel takes (128: whole lanes):
#: 2 query heads on 1 key/value head, layer 0 unrotated, layers 1-3 rotated.
LANES = "smallthinker-tiny-128"
LM_WIDTHS.setdefault(LANES, WIDTHS._replace(num_heads=2, head_dim=128))


@pytest.fixture
def small_rope_block(monkeypatch):
    """Token blocks of 16, so that T = 32 is two whole blocks and T = 40 two
    and a half; read as a call is traced (see ``small_blocks``)."""
    from mercury_tpu.ops import mercury_kernels

    monkeypatch.setattr(mercury_kernels, "ROPE_BLOCK", 16)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _as_written_before(x, hd, theta, scale, dtype):
    """An operand as the plain path makes it: ``rotate_half`` (``theta``
    None: no rotation), the scale, the cast, the transpose to head-major."""
    x = x.reshape(x.shape[0], -1, hd)
    if theta is not None:
        x = decoder.rotate_half(x, theta)
    return (x * scale).astype(dtype).transpose(1, 0, 2)


@pytest.mark.parametrize("t", [32, 40], ids=["whole-blocks", "ragged"])
@pytest.mark.parametrize("theta, scale", [
    (10_000.0, 128 ** -0.5), (10_000.0, 1.0), (None, 1.0)],
    ids=["rotated-scaled", "rotated", "neither"])
def test_the_one_pass_operand_is_the_plain_one(small_rope_block, theta, scale,
                                               t):
    """A query (rotated, scaled), a key (rotated) and a value or an
    unrotated layer's operand (cast and layout alone) from the kernel
    (interpreted) against ``rotate_half``, scale, cast and transpose as the
    plain path writes them: the float32 values and the float32 gradient of
    a scalar of the operand equal to float32's rounding (XLA:CPU contracts a
    multiply-add here and not there), so the bfloat16 operand equal but
    where that last bit crosses a rounding boundary (one element in ten
    thousand, by one bfloat16 step), at a T of whole blocks and at one that
    ends inside a block."""
    from mercury_tpu.ops import rope_heads_pallas

    rng = np.random.default_rng(t)
    x = jnp.asarray(rng.standard_normal((t, 3 * 128)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((3, t, 128)), jnp.float32)
    tables = theta and decoder.rope_tables(t, 128, theta)

    def scalar(fn):
        return lambda x: jnp.sum(fn(x).astype(jnp.float32) * c)

    def kernel(x, dtype=jnp.bfloat16):
        return rope_heads_pallas(x, tables, 128, scale, dtype)

    def plain(x, dtype=jnp.bfloat16):
        return _as_written_before(x, 128, theta, scale, dtype)

    np.testing.assert_allclose(kernel(x, jnp.float32), plain(x, jnp.float32),
                               atol=1e-6)
    got, want = kernel(x), plain(x)
    assert got.shape == (3, t, 128) and got.dtype == jnp.bfloat16
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    np.testing.assert_allclose(got, want, rtol=2 ** -7)
    assert np.mean(got != want) < 1e-3
    np.testing.assert_allclose(jax.grad(scalar(kernel))(x),
                               jax.grad(scalar(plain))(x), atol=1e-6)


def test_the_hoisted_tables_are_the_per_row_ones():
    """The tables made once a pass hold what ``rotate_half`` computes in
    each row: the same cosines, the same sines with the first half's sign
    turned; and a roll by half a head is its ``[-x2, x1]`` up to that
    sign."""
    hd, theta = 128, 1_500_000.0
    cos, sin = decoder.rope_tables(T, hd, theta)
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    angle = jnp.concatenate([angle, angle], -1)
    np.testing.assert_array_equal(cos, jnp.cos(angle))
    np.testing.assert_array_equal(sin[:, hd // 2:], jnp.sin(angle)[:, hd // 2:])
    np.testing.assert_array_equal(sin[:, :hd // 2],
                                  -jnp.sin(angle)[:, :hd // 2])
    x = jnp.asarray(np.random.default_rng(2).standard_normal((T, 3, hd)),
                    jnp.float32)
    np.testing.assert_allclose(
        x * cos[:, None] + jnp.roll(x, hd // 2, -1) * sin[:, None],
        decoder.rotate_half(x, theta), atol=1e-6)


@pytest.mark.parametrize("head_dim, rope_dim, takes", [
    (128, 128, True), (128, 0, True), (256, 256, True), (16, 16, False),
    (64, 64, False), (192, 64, False), (256, 64, False)])
def test_which_heads_the_operands_kernel_takes(head_dim, rope_dim, takes):
    """Heads of whole lanes, rotated whole or not at all: grouped-query
    heads of 128 both ways; not the tiny model's 16, not half a lane-tile,
    not latent attention's 128 + 64, nor any head rotated in part. A shape
    it refuses raises in the kernel's wrapper and never reaches it from the
    decoder."""
    from mercury_tpu.ops import rope_heads_pallas, rope_heads_takes

    assert rope_heads_takes(head_dim, rope_dim) is takes
    if not takes:
        tables = rope_dim and decoder.rope_tables(T, rope_dim, 10_000.0)
        with pytest.raises(ValueError, match="whole lanes"):
            rope_heads_pallas(jnp.zeros((T, 2 * head_dim)), tables or None,
                              head_dim, 1.0, jnp.bfloat16)


def _logits_and_grads(name, cut, use_pallas, seed=3):
    model = create_model(name, num_classes=VOCAB, cut=cut,
                         compute_dtype="float32", use_pallas=use_pallas)
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, VOCAB, (2, T)), jnp.int32)
    params = model.init(jax.random.key(seed), tokens)["params"]

    def logits(params):
        return token_logits(model.apply({"params": params}, tokens))

    jaxpr = jax.make_jaxpr(logits)(params).jaxpr
    value, grads = jax.value_and_grad(
        lambda p: jnp.sum(jnp.square(logits(p))))(params)
    return model, jaxpr, logits(params), value, grads


def test_a_decoder_with_one_pass_operands_is_the_plain_decoder(
        small_rope_block):
    """The whole tiny decoder at heads of 128, its operands from the kernel
    (``use_pallas``; T = 32 keeps the attention itself blockwise on both
    sides) against the plain forms: logits and every parameter's gradient,
    within the tolerance the splash path is held to; twelve sites one way,
    twelve the other, the kernel three times a layer and a row in the
    forward."""
    model, jaxpr, got, _, grads = _logits_and_grads(LANES, (4, 0, 4), True)
    plain, plain_jaxpr, want, _, plain_grads = _logits_and_grads(
        LANES, (4, 0, 4), False)
    assert model.operand_sites() == (12, 0)
    assert plain.operand_sites() == (0, 12)
    kernels = _kernels(jaxpr, "mercury_rope_heads")
    assert len(kernels) == 12 and all("mercury_attention" in path
                                      and "mercury_attention_proj" not in path
                                      for path in kernels)
    assert not _kernels(plain_jaxpr, "mercury_rope_heads")
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    flat, plain_flat = (jax.tree_util.tree_leaves_with_path(g)
                        for g in (grads, plain_grads))
    for (path, a), (_, b) in zip(flat, plain_flat):
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-4 * float(jnp.abs(b).max()) + 1e-6,
            err_msg=jax.tree_util.keystr(path))


def test_latent_attentions_split_heads_stay_with_the_plain_forms():
    """Latent attention's operands (128 position-free + 64 rotated columns a
    head, one shared rotated key part) are refused by the predicate from
    their shapes: with ``use_pallas`` the decoder traces no operands' kernel,
    counts six plain sites in two layers and computes, bit for bit, what it
    computes without."""
    model, jaxpr, got, value, _ = _logits_and_grads(
        LATENT, (2, 0, 4, 0, 2), True)
    plain, _, want, plain_value, _ = _logits_and_grads(
        LATENT, (2, 0, 4, 0, 2), False)
    assert model.operand_sites() == plain.operand_sites() == (0, 6)
    assert not _kernels(jaxpr, "mercury_rope_heads")
    np.testing.assert_array_equal(got, want)
    assert float(value) == float(plain_value)


@pytest.mark.parametrize("use_pallas, sites", [(True, (12, 0)),
                                               (False, (0, 12))],
                         ids=["kernel", "plain"])
def test_fit_counts_the_one_pass_operand_sites(small_rope_block, use_pallas,
                                               sites):
    """``trainer/rope_kernel_sites``, once a ``fit()``: with ``use_pallas``
    at heads the kernel takes, every operand of the four layers; without,
    none; and the step holds the kernel in the scoring pass and in the
    train pass, forward, recomputed forward and transposed."""
    from mercury_tpu.train import Trainer

    with Trainer(_config(model=LANES, use_pallas=use_pallas)) as t:
        step = jax.make_jaxpr(t.train_step)(
            t.state, t._step_x, t._step_y, t.dataset.shard_indices).jaxpr
        out = t.fit(num_epochs=2)
        assert (t._trace_facts["rope_kernel_sites"],
                t._trace_facts["rope_plain_sites"]) == sites
        events = t.tracer.snapshot()
    assert np.isfinite(out["test/eval_loss"])
    assert _operand_sites(events) == [sites]
    kernels = _kernels(step, "mercury_rope_heads")
    if not use_pallas:
        assert not kernels
        return
    assert all("mercury_attention" in path for path in kernels)
    assert any("mercury_scoring" in path for path in kernels)
    train = [path for path in kernels if "mercury_train" in path]
    assert any("transpose" in path for path in train)
    assert any("rematted_computation" in path for path in train)


# --------------------------------- the other mixer and the other routing rule
def test_the_kernel_takes_latent_heads_and_is_the_blockwise_form():
    """The kept route of latent attention on the TPU: the splash kernel
    (interpreted here) with queries and keys of 192 = 128 + 64 against
    values of 128, one query head to each key/value head, against the XLA
    form; forward and gradient. A head that is no multiple of 64, or
    values that are no multiple of 128, stay with the XLA form."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((2, 1, 256, 192)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 256, 192)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 256, 128)), jnp.float32)
    q = q * 192 ** -0.5
    assert decoder.splash_takes(256, 192, 128)
    assert not decoder.splash_takes(256, 192, 64)
    assert not decoder.splash_takes(256, 96, 128)
    want, gw = jax.value_and_grad(lambda *a: jnp.sum(jnp.square(
        decoder.blockwise_attention(*a, None, 128))), (0, 1, 2))(q, k, v)
    got, gg = jax.value_and_grad(lambda *a: jnp.sum(jnp.square(
        decoder.splash_attention(*a, None))), (0, 1, 2))(q, k, v)
    assert got.shape == () and float(want) > 0
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for a, b in zip(gg, gw):
        np.testing.assert_allclose(a, b, atol=2e-4)


def test_rotating_halves_of_permuted_columns_is_the_interleaved_rotation():
    """Latent attention rotates interleaved pairs ``(2i, 2i + 1)``; the
    program permutes the rotated columns once and rotates halves. The
    scores of a query and a key are those of the interleaved rotation
    written straight."""
    rng = np.random.default_rng(4)
    x, y = (jnp.asarray(rng.standard_normal((T, 2, 8)), jnp.float32)
            for _ in range(2))

    def interleaved(a, theta=10_000.0):
        inv = 1.0 / theta ** (np.arange(0, 8, 2) / 8)
        angle = np.arange(T)[:, None, None] * inv[None, None, :]
        even, odd = a[..., 0::2], a[..., 1::2]
        return jnp.stack([even * np.cos(angle) - odd * np.sin(angle),
                          odd * np.cos(angle) + even * np.sin(angle)],
                         -1).reshape(a.shape)

    pairs = [0, 2, 4, 6, 1, 3, 5, 7]
    np.testing.assert_array_equal(decoder.side_by_side(x), x[..., pairs])
    got = [decoder.rotate_half(decoder.side_by_side(a), 10_000.0)
           for a in (x, y)]
    np.testing.assert_allclose(got[0], interleaved(x)[..., pairs], atol=1e-5)
    np.testing.assert_allclose(
        jnp.einsum("qhd,khd->hqk", *got),
        jnp.einsum("qhd,khd->hqk", interleaved(x), interleaved(y)),
        atol=1e-4)


def _sigmoid_by_hand(r, b, k=6, scale=2.448):
    """The rule in NumPy, a token at a time."""
    s = 1.0 / (1.0 + np.exp(-np.asarray(r, np.float64)))
    chosen = np.argsort(-(s + b), axis=-1, kind="stable")[:, :k]
    picked = np.take_along_axis(s, chosen, -1)
    return chosen, scale * picked / picked.sum(-1, keepdims=True), s


def test_the_sigmoid_rule_by_hand():
    """(iv) Four tokens over 128 experts, top-6: the choice is by score plus
    bias, the weights are the UNBIASED scores of the chosen, normalised and
    scaled, and sum to 2.448; a bias that lifts a seventh-ranked expert
    over the sixth flips that choice and leaves every weight's source
    where it was; ``bias_moved`` counts exactly the flipped pairs."""
    rng = np.random.default_rng(5)
    r = jnp.asarray(rng.standard_normal((4, 128)), jnp.float32)
    flat = jnp.zeros((128,), jnp.float32)
    weights, _, _, sizes, is_held, moved = route_top_k(
        r, 6, 0, 128, flat, 2.448)
    chosen, want, s = _sigmoid_by_hand(r, np.zeros(128))
    np.testing.assert_allclose(weights, want, rtol=1e-5)
    np.testing.assert_allclose(jnp.sum(weights, -1), 2.448, rtol=1e-6)
    assert float(moved) == 0.0 and int(jnp.sum(sizes)) == 24
    assert bool(is_held.all())
    # lift token 0's seventh expert over its sixth by a bias on it alone
    order0 = np.argsort(-s[0], kind="stable")
    sixth, seventh = int(order0[5]), int(order0[6])
    bias = np.zeros(128, np.float32)
    bias[seventh] = float(s[0, sixth] - s[0, seventh]) + 1e-3
    biased, *_, moved = route_top_k(r, 6, 0, 128, jnp.asarray(bias), 2.448)
    chosen_b, want_b, _ = _sigmoid_by_hand(r, bias)
    assert seventh in chosen_b[0] and sixth not in chosen_b[0]
    np.testing.assert_allclose(biased, want_b, rtol=1e-5)
    np.testing.assert_allclose(jnp.sum(biased, -1), 2.448, rtol=1e-6)
    # the lifted expert's weight is its unbiased score's, not the biased one
    at = list(chosen_b[0]).index(seventh)
    assert float(biased[0, at]) == pytest.approx(
        2.448 * s[0, seventh] / s[0, chosen_b[0]].sum(), rel=1e-5)
    flipped = sum(len(set(a) - set(b)) for a, b in zip(chosen_b, chosen))
    assert flipped >= 1
    assert float(moved) == pytest.approx(flipped / 24)


def test_the_bound_has_a_floor_of_a_quarter_of_the_pairs():
    """Twice the uniform share where that is a quarter of the pairs or more
    (the cell of 8 of 64 experts: the rows it always had), a quarter where
    the holder is thinner (8 of 128: twice its sixteenth was too few on
    one seed of six on the chip, PERF.md section 6), all the pairs for a
    layer held whole."""
    pairs = 6 * 8192
    assert moe.pair_bound(pairs, 8, 64) == pairs // 4
    assert moe.pair_bound(pairs, 8, 128) == pairs // 4
    assert moe.pair_bound(pairs, 1, 128) == pairs // 4
    assert moe.pair_bound(pairs, 32, 64) == pairs
    assert moe.pair_bound(pairs, 24, 64) == 3 * pairs // 4


def test_no_token_is_dropped_under_the_sigmoid_rule_when_one_expert_takes_all():
    """(vi) Every token's first choice by score plus bias is held expert 1
    (the bias alone puts it there): its group holds all ``T`` pairs, none
    is cut, and its weight is from the unbiased score."""
    rng = np.random.default_rng(6)
    h = jnp.asarray(rng.standard_normal((T, 64)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((T, 16)), jnp.float32)
    bias = jnp.zeros((16,)).at[1].set(2.0).at[2:4].set(-2.0)
    gate, up, down = _experts(4)
    weights, _, _, sizes, is_held, _ = route_top_k(r, 3, 0, 4, bias, 2.448)
    assert int(sizes[1]) == T and int(sizes[2]) == int(sizes[3]) == 0
    y, _ = routed_experts(h, r, gate, up, down, 3, 0, bias=bias,
                          scale=2.448, activation=jax.nn.silu)
    chosen, want_w, _ = _sigmoid_by_hand(r, np.asarray(bias), 3)
    np.testing.assert_allclose(weights, want_w, rtol=1e-5)
    outs = jnp.stack([(jax.nn.silu(h @ gate[e]) * (h @ up[e])) @ down[e]
                      for e in range(4)], 0)
    want = sum(jnp.where((chosen[:, c] < 4)[:, None],
                         want_w[:, c, None]
                         * outs[np.clip(chosen[:, c], 0, 3), np.arange(T)],
                         0.0) for c in range(3))
    np.testing.assert_allclose(y, want, atol=1e-5)


def test_fit_leaves_the_selection_bias_where_it_was_seeded():
    """(v) ``Trainer.fit()`` on the other mixer and rule, on a share of the
    heads: the loss is finite, the log record and the tracer's instant
    carry ``bias_moved_share``, and the bias leaf (gradient exactly zero:
    it enters the choice alone) is bit for bit what it was after Adam's
    nine steps while its neighbours moved."""
    from mercury_tpu.train import Trainer

    records = []
    with Trainer(_config(model=LATENT, model_cut=(3, 0, 4, 2, 2))) as t:
        before = jax.device_get(t.state.params)
        assert before["layer1"]["q"].shape == (64, 2 * 192)
        assert "router" not in before["layer0"]
        t.logger.add_observer(lambda rec: records.append(dict(rec)))
        out = t.fit(num_epochs=9)
        after = jax.device_get(t.state.params)
        mu = jax.device_get(t.state.opt_state)
        events = t.tracer.snapshot()
    assert np.isfinite(out["test/eval_loss"])
    for layer in ("layer1", "layer2"):
        np.testing.assert_array_equal(after[layer]["router_bias"],
                                      before[layer]["router_bias"])
        assert np.abs(after[layer]["router"]
                      - before[layer]["router"]).max() > 0
    zeros = [np.asarray(leaf) for path, leaf in
             jax.tree_util.tree_leaves_with_path(mu)
             if "router_bias" in jax.tree_util.keystr(path)]
    assert zeros and all(not z.any() for z in zeros)   # mu and nu: exact 0
    assert 0.0 <= records[-1]["moe/bias_moved_share"] <= 1.0
    loads = [e for e in events if e["name"] == "trainer/moe_load"]
    assert set(loads[-1]["args"]) >= {
        "held_pair_share", "load_max_over_mean", "bounded_share",
        "bias_moved_share"}


@pytest.mark.parametrize("cut, match", [
    ((3, 0, 4, 0), "neither"),
    ((3, 0, 4, 3, 2), "key/value groups"),
    ((3, 0, 4, 0, 5), "key/value groups"),
    ((3, 14, 4, 0, 2), "does not lie inside"),
])
def test_a_cut_outside_the_model_is_refused(cut, match):
    model = create_model(LATENT, num_classes=VOCAB, cut=cut)
    with pytest.raises(ValueError, match=match):
        model.init(jax.random.key(0), jnp.zeros((1, T), jnp.int32))


def test_a_head_share_of_grouped_query_attention_is_whole_groups():
    """The five-field cut on the first mixer: a share of the query heads
    brings its key/value heads (4 query heads to 1 here, so all or
    nothing); the three-field form still means all heads."""
    model = create_model(TINY, num_classes=VOCAB, cut=(4, 0, 4, 0, 4))
    three = create_model(TINY, num_classes=VOCAB, cut=(4, 0, 4))
    shapes = [jax.eval_shape(lambda m=m: m.init(
        jax.random.key(0), jnp.zeros((1, T), jnp.int32))) for m in
        (model, three)]
    assert shapes[0] == shapes[1]
    with pytest.raises(ValueError, match="key/value groups"):
        create_model(TINY, num_classes=VOCAB, cut=(4, 0, 4, 0, 2)).init(
            jax.random.key(0), jnp.zeros((1, T), jnp.int32))
