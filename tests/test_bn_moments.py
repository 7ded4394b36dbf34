"""The statistic of the BatchNorm behind a ``Bottleneck``'s closing 1x1
convolution from its input's moments (PR 30, ``models/resnet.py``).

A pass that nothing differentiates (the scoring forward) takes the batch
mean and variance of ``conv3``'s output from the first and second moments
of ``conv3``'s INPUT; under ``jax.grad`` the unit is the plain form —
``nn.Conv`` then ``nn.BatchNorm`` — bit for bit. ``plain_bottleneck`` below
is that plain form as the parent commit wrote it: the yardstick here."""

import json
import os
from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from mercury_tpu.compat import shard_map
from mercury_tpu.config import TrainConfig
from mercury_tpu.models import resnet
from mercury_tpu.models.resnet import Bottleneck, ResNet, ResNet50
from mercury_tpu.parallel.mesh import host_cpu_mesh
from mercury_tpu.train import restore_checkpoint, save_checkpoint
from mercury_tpu.train.trainer import Trainer
from test_moments_kernel import _two_passes

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def plain_bottleneck():
    """The parent commit's ``Bottleneck`` (56b021a), under the same class
    name so that both forms read one variable tree."""

    class Bottleneck(nn.Module):
        filters: int
        strides: int
        conv: type
        norm: type
        expansion: int = 4

        @nn.compact
        def __call__(self, x):
            residual = x
            y = self.conv(self.filters, (1, 1))(x)
            y = self.norm()(y)
            y = nn.relu(y)
            y = self.conv(self.filters, (3, 3),
                          strides=(self.strides, self.strides))(y)
            y = self.norm()(y)
            y = nn.relu(y)
            y = self.conv(self.filters * self.expansion, (1, 1))(y)
            y = self.norm()(y)
            if residual.shape != y.shape:
                residual = self.conv(
                    self.filters * self.expansion, (1, 1),
                    strides=(self.strides, self.strides))(residual)
                residual = self.norm()(residual)
            return nn.relu(residual + y)

    return Bottleneck


PlainBottleneck = plain_bottleneck()


def _blocks(k, projection, dtype, axis_name=None):
    """(moments-form block, plain block, input, perturbed variables): a
    block whose closing convolution reads ``k`` channels."""
    conv = partial(nn.Conv, use_bias=False, dtype=dtype,
                   param_dtype=jnp.float32)
    norm = partial(nn.BatchNorm, use_running_average=False, momentum=0.9,
                   epsilon=1e-5, dtype=dtype, param_dtype=jnp.float32,
                   axis_name=axis_name)
    kw = dict(filters=k, strides=2 if projection else 1, conv=conv, norm=norm)
    new, plain = Bottleneck(**kw), PlainBottleneck(**kw)
    c_in = 2 * k if projection else 4 * k
    x = jax.random.normal(jax.random.key(k), (16, 8, 8, c_in), dtype)
    variables = plain.init(jax.random.key(1), x)
    assert jax.tree.all(jax.tree.map(
        np.array_equal, variables, new.init(jax.random.key(1), x)))
    # off the initialiser's symmetric point: scales, biases and running
    # averages that differ per channel
    leaves, tree = jax.tree.flatten(variables)
    keys = jax.random.split(jax.random.key(2), len(leaves))
    variables = tree.unflatten([
        a + 0.1 * jax.random.normal(key, a.shape, a.dtype)
        for a, key in zip(leaves, keys)])
    return new, plain, x, variables


def _forward(block, variables, x, **compiler_options):
    return jax.jit(lambda v, x: block.apply(v, x, mutable=["batch_stats"])
                   ).lower(variables, x).compile(
                       compiler_options=compiler_options)(variables, x)


#: XLA:CPU keeps a bf16 value that the next op widens again at f32 (its
#: "excess precision"); without it every bf16 value of the program is
#: rounded, as the chip rounds what it writes and what its MXU reads.
AS_THE_CHIP_ROUNDS = {"xla_allow_excess_precision": False}


KS = [64, 128, 256, 512]
SHORTCUTS = [pytest.param(False, id="identity"),
             pytest.param(True, id="projection")]


# --------------------------------------------------------------- the primal
@pytest.mark.parametrize("projection", SHORTCUTS)
@pytest.mark.parametrize("k", KS)
def test_forward_matches_plain_form_at_f32(k, projection):
    new, plain, x, variables = _blocks(k, projection, jnp.float32)
    (out, stats), (want, want_stats) = (
        _forward(new, variables, x), _forward(plain, variables, x))
    # Both statistics are f32 round-off away from the exact one (against
    # float64 at K=512: variance within 3.3e-5 plain, 2.6e-5 from moments),
    # so from each other: 1e-5 relative and three such steps absolute.
    close = partial(np.testing.assert_allclose, rtol=1e-5, atol=3e-5)
    close(out, want)
    # the running averages carry the unit's (mean, var): a tenth of each
    got, ref = stats["batch_stats"], want_stats["batch_stats"]
    assert jax.tree.structure(got) == jax.tree.structure(ref)
    for name in ("mean", "var"):
        close(10 * got["BatchNorm_2"][name], 10 * ref["BatchNorm_2"][name])
    # every other BatchNorm of the block is the same code on the same input
    for bn in set(ref) - {"BatchNorm_2"}:
        assert jax.tree.all(jax.tree.map(np.array_equal, got[bn], ref[bn]))


def _assert_within_a_bf16_step(out, want, differing=1e-3):
    """Where rounding falls differs, so a few elements in 100,000 land on
    the neighbouring bf16 value of the normalised map (8 bits of mantissa,
    at the binade of the block's largest output: the shortcut add may
    cancel down from there); all others are the same bits."""
    assert out.dtype == want.dtype == jnp.bfloat16
    out, want = np.asarray(out, np.float32), np.asarray(want, np.float32)
    step = 2.0 ** (np.floor(np.log2(np.abs(want).max())) - 7)
    assert np.abs(out - want).max() <= step
    assert np.mean(out != want) < differing


@pytest.mark.parametrize("projection", SHORTCUTS)
@pytest.mark.parametrize("k", KS)
def test_forward_within_a_bf16_step_of_plain_form(k, projection, monkeypatch):
    """Two links, each held to the limits PR 30 set, because XLA:CPU gives
    no one program in which both the kernel and the plain block see what
    the chip sees. A ``pallas_call``'s operand is a buffer, so the kernel
    reads ``conv2``'s map as the bf16 it is on the chip, while XLA:CPU's
    excess precision hands every other reader of it the f32 value (and
    ``h`` likewise): the kernel form is compared where all of them round.
    There the plain form's own statistic is of the ROUNDED convolution, a
    percent of the outputs and up to two steps off at these 1,024 rows
    whichever way the moments are taken (the parent's block reads the same
    there: PERF.md section 6, PR 32), so the plain form is compared as PR
    30 compared it."""
    new, plain, x, variables = _blocks(k, projection, jnp.bfloat16)
    # the kernel's one read against XLA's two passes over the same bf16
    # ``h``, every value rounded as the chip rounds it: only the order of
    # the f32 sums differs, ten times closer than the limit on the forms
    out, stats = _forward(new, variables, x, **AS_THE_CHIP_ROUNDS)
    with monkeypatch.context() as patch:
        patch.setattr(resnet, "input_moments_pallas", _two_passes)
        want, want_stats = _forward(new, variables, x, **AS_THE_CHIP_ROUNDS)
        twice, twice_stats = _forward(new, variables, x)
    _assert_within_a_bf16_step(out, want, differing=1e-4)
    for a, b in zip(jax.tree.leaves(stats), jax.tree.leaves(want_stats)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    # the moments form against the plain form, as the parent held it
    plain_out, plain_stats = _forward(plain, variables, x)
    _assert_within_a_bf16_step(twice, plain_out)
    # the statistic is of the unrounded convolution: closer than bf16 is
    for name in ("mean", "var"):
        np.testing.assert_allclose(
            10 * twice_stats["batch_stats"]["BatchNorm_2"][name],
            10 * plain_stats["batch_stats"]["BatchNorm_2"][name],
            rtol=2e-3, atol=2e-3)


def test_forward_holds_the_gram_product_and_no_statistic_of_the_output():
    new, _, x, variables = _blocks(64, False, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda v, x: new.apply(v, x, mutable=["batch_stats"]))(variables, x)
    (unit,) = [e for e in jaxpr.eqns if e.primitive.name == "custom_vjp_call"]
    inner = unit.params["call_jaxpr"].jaxpr.eqns
    # one kernel call a closing unit: conv2's raw map over the 8*8 positions,
    # two stacked -> (sum h, h h^T) of the 128 stacked channels
    (call,) = [e for e in inner if e.primitive.name == "pallas_call"]
    assert [v.aval.str_short(short_dtypes=True)
            for v in call.invars[:1] + call.outvars] == [
        "bf16[32,128,16]", "f32[128,1]", "f32[128,128]"]
    # The only reductions over a map are BatchNorm_1's (mean and mean of
    # squares of conv2's 64-wide output): none over h, none over the
    # closing convolution's 256-wide output.
    sums = [e.invars[0].aval.shape for e in inner
            if e.primitive.name == "reduce_sum" and e.invars[0].aval.ndim == 4]
    assert sums == [(16, 8, 8, 64)] * 2, sums
    assert not [e for e in inner if e.primitive.name == "dot_general"
                and e.invars[0].aval.shape[0] == 16 * 8 * 8]


# ------------------------------------------------------ the differentiated
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("projection", SHORTCUTS)
@pytest.mark.parametrize("k", KS)
def test_gradient_is_bit_identical_to_plain_form(k, projection, dtype):
    new, plain, x, variables = _blocks(k, projection, dtype)

    def loss(block, params, x):
        out, stats = block.apply({**variables, "params": params}, x,
                                 mutable=["batch_stats"])
        return jnp.sum(jnp.square(out.astype(jnp.float32))), (out, stats)

    def grads(block):
        return jax.jit(jax.grad(partial(loss, block), argnums=(0, 1),
                                has_aux=True))(variables["params"], x)

    got, want = grads(new), grads(plain)
    # gradients of every parameter and of the input, the block's output,
    # and the running averages the differentiated pass leaves
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert np.array_equal(a, b), jax.tree_util.keystr(path)


# ------------------------------------------------------------ synced moments
@pytest.mark.parametrize("projection", SHORTCUTS)
def test_synced_moments_are_the_moments_of_all_rows(projection, monkeypatch):
    """With ``bn_axis_name`` the ``pmean`` of the shards' moments is the
    statistic of the concatenated rows: four devices, four rows each,
    against one device holding all sixteen."""
    mesh = host_cpu_mesh(4)
    synced, _, x, variables = _blocks(64, projection, jnp.float32,
                                      axis_name="data")
    single, plain, _, _ = _blocks(64, projection, jnp.float32)

    def per_shard(v, rows):
        return synced.apply(v, rows, mutable=["batch_stats"])

    def sharded(check_vma):
        return jax.jit(shard_map(
            per_shard, mesh=mesh, in_specs=(P(), P("data")),
            out_specs=(P("data"), P()), check_vma=check_vma))

    # Pallas's interpreter cannot run a kernel on a block typed as varying
    # (its loops carry untyped values), so off the chip the values come with
    # the check off, as the step's own shard_map has it, and the check is
    # made of the trace, with the moments by XLA's two passes: the statistic
    # leaves the unit replicated. The kernel's own typing under the check is
    # Mosaic's case: tests/test_tpu_aot.py::test_synced_unit_checks_vma.
    with monkeypatch.context() as patch:
        patch.setattr(resnet, "input_moments_pallas", _two_passes)
        jax.eval_shape(sharded(True), variables, x)
    out, stats = sharded(False)(variables, x)
    want, want_stats = _forward(single, variables, x)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(stats), jax.tree.leaves(want_stats)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    # ... and so is the plain form's, which the differentiated pass runs
    ref, _ = _forward(plain, variables, x)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=3e-5)


# ----------------------------------------------------- the whole ResNet-50
@pytest.fixture(scope="module")
def resnet50_logits():
    """Logits of a random-weight ResNet-50 over 64 image-like rows, in
    train mode: {(form, dtype): [64, 100] f32}."""
    x = jax.random.uniform(jax.random.key(3), (64, 32, 32, 3))
    x = (x - 0.5) / 0.25
    out = {}
    variables = None
    for dtype in (jnp.float32, jnp.bfloat16):
        for form, block in (("moments", Bottleneck),
                            ("plain", PlainBottleneck)):
            model = ResNet(stage_sizes=[3, 4, 6, 3], block_cls=block,
                           num_classes=100, compute_dtype=dtype)
            if variables is None:
                variables = jax.jit(partial(model.init, train=True))(
                    jax.random.key(4), x[:2])
            out[form, jnp.dtype(dtype).name] = np.asarray(jax.jit(
                lambda v, x, model=model: model.apply(
                    v, x, train=True, mutable=["batch_stats"])[0]
            )(variables, x))
    return out


def _rms(a):
    return float(np.sqrt(np.mean(np.square(a, dtype=np.float64))))


def test_resnet50_forward_at_f32_is_the_plain_forward(resnet50_logits):
    want = resnet50_logits["plain", "float32"]
    gap = _rms(resnet50_logits["moments", "float32"] - want) / _rms(want)
    assert gap < 1e-4, gap


def test_resnet50_forward_at_bf16_is_as_close_to_f32_as_plain(resnet50_logits):
    want = resnet50_logits["plain", "float32"]
    plain = _rms(resnet50_logits["plain", "bfloat16"] - want)
    moments = _rms(resnet50_logits["moments", "bfloat16"] - want)
    assert moments <= 1.1 * plain, (moments / _rms(want), plain / _rms(want))


# ----------------------------------------------- the tree, and checkpoints
def test_resnet50_variable_tree_is_the_parents():
    """Names, shapes and dtypes of ``ResNet50().init(...)``, against the
    list taken from the parent commit (56b021a)."""
    with open(os.path.join(FIXTURES, "resnet50_variable_tree.json")) as f:
        golden = json.load(f)
    variables = jax.eval_shape(
        lambda: ResNet50(num_classes=100).init(
            jax.random.key(0), jnp.zeros((2, 32, 32, 3)), train=True))
    mine = [[jax.tree_util.keystr(path, simple=True, separator="/"),
             list(leaf.shape), leaf.dtype.name]
            for path, leaf in jax.tree_util.tree_leaves_with_path(variables)]
    assert mine == golden


def test_checkpoint_of_the_plain_tree_restores(tmp_path):
    """A checkpoint written from the parent's form of the model restores
    into this one's variables, and the restored model runs."""
    kw = dict(stage_sizes=[1, 1], num_filters=8, num_classes=10,
              compute_dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(5), (8, 8, 8, 3))
    old = ResNet(block_cls=PlainBottleneck, **kw).init(
        jax.random.key(6), x, train=True)
    new_model = ResNet(block_cls=Bottleneck, **kw)
    template = new_model.init(jax.random.key(7), x, train=True)
    save_checkpoint(str(tmp_path), old, 3)
    restored, step = restore_checkpoint(str(tmp_path), template)
    assert step == 3
    assert jax.tree.structure(restored) == jax.tree.structure(old)
    assert jax.tree.all(jax.tree.map(np.array_equal, restored, old))
    logits, _ = new_model.apply(restored, x, train=True,
                                mutable=["batch_stats"])
    assert np.all(np.isfinite(logits))
    # the running-average path never meets the unit: the same evaluation
    plain = ResNet(block_cls=PlainBottleneck, **kw)
    assert np.array_equal(new_model.apply(restored, x, train=False),
                          plain.apply(old, x, train=False))


# ------------------------------------------------------------- the counter
@pytest.mark.parametrize("model,fields,units", [
    ("resnet50", dict(use_importance_sampling=True), 16),
    ("resnet50", dict(use_importance_sampling=False), 0),
    ("resnet18", dict(use_importance_sampling=True), 0),
], ids=["resnet50-is", "resnet50-uniform", "resnet18-is"])
def test_step_counts_its_moment_units_as_it_is_traced(model, fields, units):
    config = TrainConfig(
        model=model, dataset="synthetic", world_size=1, batch_size=4,
        presample_batches=2, log_every=0, eval_every=0, heartbeat_every=0,
        **fields)
    with Trainer(config, mesh=host_cpu_mesh(1)) as tr:
        assert tr._trace_facts == {}
        jax.eval_shape(tr.train_step, tr.state, tr._step_x, tr._step_y,
                       tr.dataset.shard_indices)
        assert tr._trace_facts.get("bn_moment_units", 0) == units
