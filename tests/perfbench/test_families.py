"""A model family is files: what every file under ``perfbench/references/``
offers, what the rest of the harness may know of a family (nothing), and a
family the program does not have, integer tokens with a per-sequence loss,
driven through the shared functions as they are."""

import ast
import glob
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import check, reference, replay

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FAMILY_FILES = sorted(
    os.path.relpath(p, REPO) for p in glob.glob(
        os.path.join(REPO, "perfbench", "references", "*.py"))
    if not os.path.basename(p).startswith("_"))
SHARED_SOURCES = sorted(
    os.path.relpath(p, REPO) for p in glob.glob(
        os.path.join(REPO, "perfbench", "**", "*.py"), recursive=True)
    if os.sep + "references" + os.sep not in p
    and os.sep + "_" not in os.path.relpath(p, REPO))
TOKENS = {"file": "tests/perfbench/token_family.py", "d_model": 8,
          "sampling": {"is_alpha": 0.5, "ema_alpha": 0.9},
          "adam": {"b1": 0.9, "b2": 0.999, "eps": 1e-8}}
VOCAB, SEQ = 11, 6


# ------------------------------------------------------------ the interface
@pytest.mark.parametrize("path", FAMILY_FILES + [TOKENS["file"]],
                         ids=lambda p: os.path.basename(p)[:-3])
def test_a_family_file_offers_the_whole_interface(path):
    assert len(FAMILY_FILES) >= 3
    module = reference.family({"file": path})
    for name in reference.INTERFACE:
        assert callable(getattr(module, name)), name
    assert module.__doc__ and "fp8" in module.__doc__  # where it rounds


def test_a_file_short_of_the_interface_is_refused(tmp_path):
    path = tmp_path / "half.py"
    path.write_text("def forward(*a):\n    return None\n")
    with pytest.raises(TypeError, match="offers no prepare"):
        reference.family({"file": str(path)})


def _code_strings(tree):
    """Every string constant of a module but the docstrings."""
    docstrings = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            body = node.body
            if (body and isinstance(body[0], ast.Expr)
                    and isinstance(body[0].value, ast.Constant)
                    and isinstance(body[0].value.value, str)):
                docstrings.add(id(body[0].value))
    return [n.value for n in ast.walk(tree)
            if isinstance(n, ast.Constant) and isinstance(n.value, str)
            and id(n) not in docstrings]


def _keys_read_of(tree, group="reference", handle="arch"):
    """Constant keys read of the configuration's ``reference`` group: of a
    name ``arch`` (the handle every shared function gives it) or of a
    ``[...]["reference"]`` subscript, by ``[...]`` or ``.get(...)``."""
    def is_group(node):
        return ((isinstance(node, ast.Name) and node.id == handle)
                or (isinstance(node, ast.Subscript)
                    and isinstance(node.slice, ast.Constant)
                    and node.slice.value == group))

    keys = set()
    for node in ast.walk(tree):
        if (isinstance(node, ast.Subscript) and is_group(node.value)
                and isinstance(node.slice, ast.Constant)):
            keys.add(node.slice.value)
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                and node.func.attr == "get" and is_group(node.func.value)
                and node.args and isinstance(node.args[0], ast.Constant)):
            keys.add(node.args[0].value)
    return keys


@pytest.mark.parametrize("path", SHARED_SOURCES)
def test_shared_code_knows_no_family(path):
    """Outside ``references/`` no code of the harness names a family, or
    reads of the configuration's ``reference`` group anything but ``file``,
    ``sampling`` and ``adam``, or a key that is one family's."""
    assert {"perfbench/run.py", "perfbench/reference.py",
            "perfbench/replay.py", "perfbench/check.py",
            "perfbench/flops.py"} <= set(SHARED_SOURCES)
    tree = ast.parse(open(os.path.join(REPO, path)).read())
    families = {os.path.basename(f)[:-3] for f in FAMILY_FILES}
    family_keys = {"family", "mean", "std", "stage_sizes", "block",
                   "image_size", "num_heads", "pad"}
    for text in _code_strings(tree):
        assert not any(f in text.lower() for f in families), (path, text)
        assert text not in family_keys, (path, text)
    assert _keys_read_of(tree) <= {"file", "sampling", "adam"}, path


def test_the_test_of_shared_code_sees_what_it_should():
    tree = ast.parse('"""resnet"""\n'
                     'def f(arch, cfg):\n'
                     '    """mean"""\n'
                     '    a = arch["block"], arch.get("std")\n'
                     '    return cfg["reference"]["mean"], "resnet50"\n')
    assert sorted(_code_strings(tree)) == ["block", "mean", "reference",
                                           "resnet50", "std"]
    assert _keys_read_of(tree) == {"block", "std", "mean"}


# ------------------------------------ the evaluate side, one block at a time
def _spy_on_forward(monkeypatch, arch):
    """The leading sizes the family's ``forward`` is handed from here on
    (under ``jit``: once a traced shape)."""
    fam = reference.family(arch)
    seen, real = [], fam.forward

    def spy(params, model_state, inputs, arch, quantize=None):
        seen.append(inputs.shape[0])
        return real(params, model_state, inputs, arch, quantize)

    monkeypatch.setattr(fam, "forward", spy)
    return seen


def test_the_evaluate_side_hands_forward_one_block_at_a_time(monkeypatch):
    """No array of a split's outputs is ever built: ``forward`` sees
    ``block_rows`` rows at most, and the mean is the float64 mean of the
    per-example values."""
    arch = dict(TOKENS)
    fam = reference.family(arch)
    params, x, y = _token_data(n=23)
    real = fam.forward
    seen = _spy_on_forward(monkeypatch, arch)
    got = reference.eval_loss(params, None, x, y, arch, block_rows=5)
    assert seen and max(seen) <= 5 and 3 in seen  # 23 = 4 x 5 + 3
    want = np.asarray(fam.example_loss(real(params, None, x, arch), y),
                      np.float64).mean()
    assert got == pytest.approx(want, rel=1e-6)
    assert check.block_rows({"block_rows": 5}, 1000) == 5
    assert check.block_rows({}, 1000) == 250 and check.block_rows({}, 23) == 64


# ----------------------------------------------------- integer-token fixture
def _token_data(n, seed=0):
    rng = np.random.default_rng(seed)
    params = {"embed": rng.normal(0, 0.5, (VOCAB, TOKENS["d_model"])
                                  ).astype(np.float32)}
    x = rng.integers(0, VOCAB, (n, SEQ + 1)).astype(np.int32)
    return params, x[:, :-1], x[:, 1:]      # the labels: the inputs shifted


def _token_steps(params, x, y, fields, seed=1):
    """What a ``replay.Recorder`` would keep of STEPS + 1 steps of a plain
    ``jax.numpy`` + optax Mercury train step on token rows (the reweighted
    per-sequence loss, Adam under the cosine schedule): hand-made recorded
    steps, no ``Trainer`` behind them. The first state also carries the
    stream, key and EMA the first replayed step's pool is rebuilt from, and
    the batch that step draws comes from that very pool."""
    import optax
    from mercury_tpu.train.state import PendingBatch, make_optimizer

    fam = reference.family(TOKENS)
    tx = make_optimizer("adam", fields["base_lr"],
                        fields["steps_per_epoch"] * fields["num_epochs"])
    opt_state = tx.init(params)
    rng = np.random.default_rng(seed)
    batch, pool = fields["batch_size"], (fields["batch_size"]
                                         * fields["presample_batches"])

    def loss_fn(p, b):
        z = fam.forward(p, None, b.images[0], TOKENS)
        return jnp.mean(fam.example_loss(z, b.labels[0]) / b.scaled_probs[0])

    def some_batch():
        rows = rng.choice(len(x), batch, replace=False)
        return PendingBatch(
            images=x[rows][None], labels=y[rows][None],
            scaled_probs=rng.uniform(0.5, 2.0, (1, batch)).astype(np.float32))

    steps, pending = [], some_batch()
    for i in range(replay.STEPS + 1):
        loss, grads = jax.value_and_grad(loss_fn)(params, pending)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        adam = replay._adam_state(opt_state)
        kept = dict(metrics={"train/loss": float(loss)},
                    mu=replay._host(adam.mu), nu=replay._host(adam.nu),
                    count=int(adam.count), params=replay._host(params))
        if i == 0:
            # the state the next step's pool is drawn from, and its draw
            key = jax.random.key(seed)
            kept.update(
                rng=np.asarray(jax.random.key_data(key))[None],
                stream=SimpleNamespace(perm=rng.permutation(len(x))[None],
                                       cursor=np.array([3])),
                ema=SimpleNamespace(value=np.array([0.0]),
                                    count=np.array([0])))
        steps.append(kept)
        pending = some_batch()
        if i == 1:
            # this step scored its pool with the parameters it trained from
            # and drew the next step's batch from it
            start = steps[0]
            inputs, labels, _, scaled = reference.score_pool(
                start["params"], key, start["stream"].perm[0], 3, 0.0, 0,
                x, y, np.arange(len(x)), TOKENS, pool)
            drawn = rng.choice(pool, batch, replace=False)
            pending = PendingBatch(
                images=inputs[drawn][None], labels=labels[drawn][None],
                scaled_probs=scaled[drawn][None].astype(np.float32))
        kept["pending"] = replay._host(pending)
    return steps


def test_integer_tokens_pass_through_the_shared_functions():
    fields = {"world_size": 1, "base_lr": 0.01, "steps_per_epoch": 1,
              "num_epochs": 1000, "batch_size": 4, "presample_batches": 3}
    params, x, y = _token_data(n=40)
    assert x.dtype == np.int32 and y.shape == x.shape

    loss, grads = reference.make_loss_and_grad(TOKENS)(
        params, x[:4], y[:4], np.ones(4, np.float32))
    assert np.isfinite(float(loss)) and grads["embed"].shape == (VOCAB, 8)

    steps = _token_steps(params, x, y, fields)
    assert steps[1]["pending"][replay.INPUTS].dtype == np.int32
    # the pool of the first replayed step, rebuilt: every drawn row is found
    # again by exact match, and its N p_i is the reference's own
    gaps = replay.compare(replay.system_steps(steps, TOKENS), steps,
                          (x, y, np.arange(len(x))[None]), TOKENS, fields)
    assert gaps["weight_gap"] == pytest.approx(0.0, abs=1e-6)
    assert max(gaps["loss_gap"], gaps["grad_norm_gap"],
               gaps["update_norm_gap"]) < 1e-3, gaps
    # a drawn row that is one token off is in no pool
    off = steps[1]["pending"]
    inputs = off[replay.INPUTS].copy()
    inputs[0, 0, 0] = (inputs[0, 0, 0] + 1) % VOCAB
    steps[1]["pending"] = off._replace(images=inputs)
    assert replay.reference_weights(
        steps, (x, y, np.arange(len(x))[None]), TOKENS, fields) is None

    # the inference side on a sample of 4 rows, whatever the outputs' shape
    limits = {"sample_rows": 4}
    idx = check.sample_indices(7, len(x), check.sample_rows(limits))
    assert len(idx) == 4
    ref = reference.outputs(params, None, x[idx], TOKENS)
    assert ref.shape == (4, SEQ, VOCAB)
    assert check.logit_gap(ref, ref) == 0.0
    lower = reference.outputs(params, None, x[idx], TOKENS, quantize="fp8")
    assert check.logit_gap(lower, ref) > 1e-3
    # and the evaluate side, block by block
    assert reference.eval_loss(params, None, x, y, TOKENS, block_rows=16) > 0


def test_rows_match_as_flat_vectors():
    pool = np.arange(60, dtype=np.int32).reshape(10, 6)
    assert (replay._match_rows(pool[[7, 2]], pool) == [7, 2]).all()
    near = pool[[7]].copy()
    near[0, 3] += 1                       # integers: the same or not at all
    assert (replay._match_rows(near, pool) == [-1]).all()
    images = np.random.default_rng(0).normal(size=(10, 4, 4, 3))
    drawn = images[[5, 1]] + 1e-6         # floats: within a rounding
    assert (replay._match_rows(drawn, images) == [5, 1]).all()
    assert (replay._match_rows(images[[5]] + 0.1, images) == [-1]).all()


# ------------------------------------- the family transformer_classifier
TRANSFORMER = {"file": "perfbench/references/transformer_classifier.py",
               "num_heads": 2, "d_model": 32, "num_layers": 2,
               "mlp_ratio": 4}
SEQUENCES = {"seq_len": 12, "feature_dim": 5, "num_classes": 7,
             "reference": TRANSFORMER}


def _transformer(compute_dtype):
    from mercury_tpu.models import create_model

    return create_model("transformer", num_classes=7,
                        compute_dtype=compute_dtype, d_model=32, num_heads=2,
                        num_layers=2, max_len=16)


def _seeded_transformer(seed=0):
    """Seeded weights with the biases and LayerNorm terms moved off their
    0/1 initial values, so every term of the equations counts."""
    x = jnp.zeros((1, 12, 5))
    variables = _transformer("float32").init(jax.random.key(seed), x,
                                             train=False)
    leaves, tree = jax.tree.flatten_with_path(variables)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    out = []
    for (path, leaf), k in zip(leaves, keys):
        last = jax.tree_util.keystr(path)
        if "scale" in last:
            leaf = jax.random.uniform(k, leaf.shape, minval=0.5, maxval=1.5)
        elif "bias" in last:
            leaf = 0.2 * jax.random.normal(k, leaf.shape)
        out.append(leaf)
    return jax.tree.unflatten(tree, out)


def test_transformer_reference_follows_the_models_equations():
    """``references/transformer_classifier.py`` against
    ``models/transformer.py`` in float32 at tiny size: the same equations,
    two implementations; and its FLOP count against XLA's of its own
    forward, which adds the LayerNorms, softmax and GELU it leaves out."""
    fam = reference.family(TRANSFORMER)
    v = _seeded_transformer()
    assert set(v) == {"params"}            # no model state
    x = jax.random.normal(jax.random.key(9), (4, 12, 5))
    with jax.default_matmul_precision("highest"):
        want = _transformer("float32").apply(v, x, train=False)
    for model_state in (None, {}):         # one mode
        got = fam.forward(v["params"], model_state, x, TRANSFORMER)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)
    assert fam.prepare(x, TRANSFORMER) is not None
    assert fam.augment(jax.random.key(0), x, TRANSFORMER) is x
    count = fam.fwd_flops_per_example(SEQUENCES)
    cost = jax.jit(lambda p, x: fam.forward(p, None, x, TRANSFORMER)).lower(
        v["params"], x[:1]).cost_analysis()
    assert count < cost["flops"] < 1.15 * count


def test_transformer_check_passes_as_stated_and_fails_a_precision_lower():
    """The family's numbers at tiny size, as ``test_perfbench`` holds the
    ResNet's: the system side in bfloat16 compute (what a configuration
    would state) and in float32 lies well under the control's (the same
    forward with the inputs and weights of its matrix products rounded to
    fp8), on the inference check's number and on the pool's ``N p_i``, and
    a limit between the two passes the one and fails the other."""
    fam = reference.family(TRANSFORMER)
    v = _seeded_transformer(seed=3)
    x = np.asarray(jax.random.normal(jax.random.key(4), (64, 12, 5)))
    y = jax.random.randint(jax.random.key(5), (64,), 0, 7)
    sampling = {"is_alpha": 0.5, "ema_alpha": 0.9}

    def weights(logits):
        return reference.scaled_probs(fam.example_loss(logits, y), 0.0, 0,
                                      sampling)

    ref = reference.outputs(v["params"], {}, x, TRANSFORMER)
    lower = reference.outputs(v["params"], {}, x, TRANSFORMER,
                              quantize="fp8")
    control = {"logit_gap": check.logit_gap(lower, ref),
               "weight_gap": replay.weight_gap(weights(lower), weights(ref))}
    for dtype, under in (("float32", 1e-4), ("bfloat16", None)):
        system = _transformer(dtype).apply(v, jnp.asarray(x), train=False)
        sound = {"logit_gap": check.logit_gap(system, ref),
                 "weight_gap": replay.weight_gap(weights(system),
                                                 weights(ref))}
        for name in sound:
            if under:
                assert sound[name] < under, (dtype, sound)
                continue
            assert 3 * sound[name] < control[name], (sound, control)
            limit = (sound[name] * control[name]) ** 0.5
            assert check.Number(name, sound[name], limit).ok
            assert not check.Number(name, control[name], limit).ok
