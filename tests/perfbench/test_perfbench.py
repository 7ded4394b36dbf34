"""The benchmark off the chip: its whole command at tiny size on the CPU
mesh, its data files, its reference, its FLOP counts and its trace
reduction. On the chip the same code runs at the cells' real sizes
(``python3 perfbench/run.py``); nothing here is a device measurement."""

import glob
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import cell as cell_mod
from perfbench import check, reference, replay, run, trace_reduce

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURE = os.path.join(REPO, "tests", "fixtures", "profile_trace.json")
RESNET_FILE = "perfbench/references/resnet.py"
RESNET = reference.family({"file": RESNET_FILE})
MANIFEST = cell_mod.manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

#: The whole command shrunk to the program's debug CNN: same code path
#: (``Trainer.fit`` under the harness, reference check, result line), a
#: job that compiles in seconds on the CPU.
TINY = {
    "train_config": {"model": "smallcnn", "dataset": "synthetic",
                     "batch_size": 8, "presample_batches": 2,
                     "compute_dtype": "float32", "log_every": 10},
    "steps_per_call": 10, "trace_calls": 2,
    "reference": {"file": "perfbench/references/smallcnn.py",
                  "family": "smallcnn",
                  "mean": [0.49139968, 0.48215827, 0.44653124],
                  "std": [0.24703233, 0.24348505, 0.26158768],
                  "sampling": {"is_alpha": 0.5, "ema_alpha": 0.9, "pad": 4},
                  "adam": {"b1": 0.9, "b2": 0.999, "eps": 1e-8}},
    # float32 on both sides: only summation order differs
    "check": {"logit_gap_limit": 1e-4, "eval_loss_gap_limit": 1e-3,
              "loss_gap_limit": 1e-4, "grad_norm_gap_limit": 1e-3,
              "update_norm_gap_limit": 1e-3, "weight_gap_limit": 1e-4,
              "window_update_rms_floor": 1e-5},
}
#: The same command over a second family, from what the program runs
#: today: the pre-LN Transformer classifier on the program's seeded feature
#: sequences ``[N, 32, 16]`` (float32 rows, no augmentation, no BatchNorm
#: state). ``update_norm_gap``'s worst leaf is the first block's key bias:
#: its gradient is zero in exact arithmetic (softmax takes no notice of a
#: constant added to every score of a row), so Adam normalises rounding
#: noise on both sides: 0.003-0.005 here, where a stopped optimizer reads 1.
TRANSFORMER = {
    "train_config": {"model": "transformer", "dataset": "synthetic_seq_hard",
                     "augmentation": "none", "batch_size": 8,
                     "presample_batches": 4, "compute_dtype": "float32",
                     "log_every": 10},
    "steps_per_call": 10, "trace_calls": 2,
    "reference": {"file": "perfbench/references/transformer_classifier.py",
                  "num_heads": 4,
                  "sampling": {"is_alpha": 0.5, "ema_alpha": 0.9},
                  "adam": {"b1": 0.9, "b2": 0.999, "eps": 1e-8}},
    "check": {"sample_rows": 32, "block_rows": 100,
              "logit_gap_limit": 1e-4, "eval_loss_gap_limit": 1e-3,
              "loss_gap_limit": 1e-4, "grad_norm_gap_limit": 1e-3,
              "update_norm_gap_limit": 0.05, "weight_gap_limit": 1e-4,
              "window_update_rms_floor": 1e-5},
}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "checks"}
ALL_CHECKS = {"loss_gap", "grad_norm_gap", "update_norm_gap", "logit_gap",
              "eval_loss_gap", "window_update_rms", "nonfinite_losses",
              "steps_advanced", "compiles_in_window"}


def _tiny(base=TINY, **train_config):
    out = dict(base)
    out["train_config"] = dict(base["train_config"], **train_config)
    return out


def _last_line(capsys):
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1])


# ------------------------------------------------------- the whole command
@pytest.mark.parametrize("world", [1, 4])
def test_whole_command_tiny(capsys, world):
    """Every cell's command body on the CPU mesh (one device, and four
    virtual devices): set-up with the replay's recorder, window, reference
    check, result line. On one worker the replay rebuilds the pool too."""
    result = run.run_cell(CELLS[0], 2 ** 31 + 5, 0.5, False,
                          rehearsal=_tiny(world_size=world))
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert set(line) == RESULT_KEYS and line == result
    compared = set(re.findall(r"check (\w+): .* -> ok", out))
    assert compared == ALL_CHECKS | ({"weight_gap"} if world == 1 else set())
    # each number beside its limit: last in the line, and the last lines of
    # standard error
    assert list(line)[-1] == "checks" and set(line["checks"]) == compared
    assert all(c["ok"] and set(c) == {"value", "rule", "limit", "ok"}
               for c in line["checks"].values())
    assert [re.match(r"\[perfbench\] check (\w+): ", e).group(1) for e in
            err.strip().splitlines()[-len(compared):]] == list(line["checks"])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["attempted"] % 10 == 0
    want = {m["name"] for m in MANIFEST["end_to_end"]}
    assert set(line["metrics"]) == want
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"}, name
    assert line["metrics"]["setup_s"]["value"] > 0
    assert line["metrics"]["train_examples_per_s"]["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}


def test_traced_run_tiny(capsys):
    """``--trace 1``: ``trace_calls`` traced ``fit()`` calls; the CPU has
    no device lanes, so the trace readers find nothing and leave their
    metrics out — the host-side ones are there."""
    run.run_cell(CELLS[0], 7, 0.5, True, rehearsal=_tiny())
    line = _last_line(capsys)
    assert set(line) == RESULT_KEYS | {"breakdown"}
    assert line["correct"] is True and line["attempted"] == 20
    per_layer = {m["name"] for m in MANIFEST["per_layer"]}
    assert {"log_gate_ms_per_step",
            "compiles_in_window"} <= set(line["metrics"]) <= per_layer
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"busy_s", "window_s"} <= set(line["device"])


@pytest.mark.parametrize("world, trace", [(1, False), (4, False), (1, True)],
                         ids=["one_worker", "four_workers", "traced"])
def test_whole_command_transformer(capsys, world, trace):
    """The same command body over the second family: float32 sequence
    rows, no augmentation, an empty ``batch_stats``, a sample of 32 rows,
    the evaluate side in blocks of 100: ``correct: true`` as stated."""
    run.run_cell(CELLS[0], 2 ** 31 + 11, 0.3, trace,
                 rehearsal=_tiny(TRANSFORMER, world_size=world))
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert set(line) == RESULT_KEYS | ({"breakdown"} if trace else set())
    compared = set(re.findall(r"check (\w+): .* -> ok", out))
    assert compared == ALL_CHECKS | ({"weight_gap"} if world == 1 else set())
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["attempted"] % 10 == 0
    if world == 1:
        assert "8 of 8 drawn rows found in the rebuilt pool of 32" in out


def test_the_fp8_control_fails_the_transformers_check(capsys):
    """The whole command with the control's readings beside the program's
    (``readings.py``'s call): the plain reference in fp8, put in the
    program's place, is over the limits the program passes, on the
    inference check's number and on the pool's weights."""
    result = run.run_cell(CELLS[0], 13, 0.3, False,
                          rehearsal=_tiny(TRANSFORMER), control=True)
    capsys.readouterr()
    assert result["correct"] is True
    limits = TRANSFORMER["check"]
    for name in ("logit_gap", "weight_gap"):
        limit = limits[f"{name}_limit"]
        assert check.Number(name, result["numbers"][name], limit).ok
        assert not check.Number(name, result["control"][name], limit).ok
        assert result["control"][name] > 100 * result["numbers"][name]


def _frozen(trainer):
    """A step that returns its state unchanged."""
    real, seen = trainer.train_step, {}

    def step(state, x, y, idx):
        if not seen:
            _, seen["metrics"] = real(jax.tree.map(jnp.copy, state), x, y,
                                      idx)
        return state, seen["metrics"]

    trainer.train_step = step


def _keeps_params(trainer, after=0):
    """A step that does everything but apply its update (a learning rate
    of zero), from its ``after``-th call on."""
    real, calls = trainer.train_step, [0]

    def step(state, x, y, idx):
        calls[0] += 1
        old = jax.tree.map(jnp.copy, state.params)
        new, metrics = real(state, x, y, idx)
        return (new.replace(params=old) if calls[0] > after else new), metrics

    trainer.train_step = step


def _reweights(trainer, weights):
    """A step that trains on other importance weights than it drew."""
    real = trainer.train_step

    def step(state, x, y, idx):
        pending = state.pending
        return real(state.replace(pending=pending._replace(
            scaled_probs=weights(pending.scaled_probs))), x, y, idx)

    trainer.train_step = step


def _half_batch(trainer):
    """Half of the drawn batch left out of the loss."""
    _reweights(trainer, lambda sp: sp.at[:, sp.shape[1] // 2:].set(1e30))


def _no_reweighting(trainer):
    """The drawn batch trained on as if it were drawn uniformly."""
    _reweights(trainer, jnp.ones_like)


def _evaluates(trainer, loss_of):
    real = trainer.evaluate

    def evaluate(*args, **kwargs):
        out = real(*args, **kwargs)
        ds = trainer.dataset
        out["test/eval_loss"] = loss_of(np.asarray(ds.x_test),
                                        np.asarray(ds.y_test))
        return out

    trainer.evaluate = evaluate


def _evaluate_drops_rows(trainer, arch=TINY["reference"]):
    """An evaluate that leaves out the test split's second half."""
    fam = reference.family(arch)
    _evaluates(trainer, lambda x, y: float(fam.eval_example_loss(
        trainer.predict(x[:len(x) // 2]), y[:len(x) // 2]).mean()))


def _evaluate_in_training_mode(trainer, arch=TINY["reference"]):
    """An evaluate that normalizes by the batch's own statistics."""
    fam = reference.family(arch)

    def loss_of(x, y):
        z = fam.forward(jax.device_get(trainer.state.params), None,
                        fam.prepare(jnp.asarray(x), arch), arch)
        return float(fam.eval_example_loss(z, y).mean())

    _evaluates(trainer, loss_of)


@pytest.mark.parametrize("fault, failing", [
    (_frozen, {"steps_advanced", "update_norm_gap", "window_update_rms"}),
    (_keeps_params, {"update_norm_gap", "window_update_rms"}),
    # sound through set-up, broken from the window's first step on
    (lambda t: _keeps_params(t, after=20), {"window_update_rms"}),
    (_half_batch, {"loss_gap", "grad_norm_gap"}),
    (_no_reweighting, {"loss_gap"}),
    (_evaluate_drops_rows, {"eval_loss_gap"}),
    (_evaluate_in_training_mode, {"eval_loss_gap"}),
], ids=["frozen", "zero_lr", "stops_updating_in_the_window", "half_batch",
        "no_reweighting", "evaluate_drops_rows", "evaluate_in_training_mode"])
def test_a_broken_path_is_not_correct(capsys, monkeypatch, fault, failing):
    """The timed path broken underneath drives the rest of a run, past the
    harness's look for a chip, and comes out ``correct: false`` on the
    numbers that are there to catch that fault."""
    failed = _failed_checks_of_a_broken_run(capsys, monkeypatch, fault,
                                            3, _tiny())
    assert failing <= failed, (failing, failed)


def _failed_checks_of_a_broken_run(capsys, monkeypatch, fault, seed,
                                   rehearsal):
    """The names of the numbers over their limit in a run of the whole
    command whose trainer ``fault`` has broken (None: broken elsewhere);
    ``correct`` has to be false."""
    build = run.build_trainer

    def build_broken(fields):
        trainer = build(fields)
        if fault:
            fault(trainer)
        return trainer

    monkeypatch.setattr(run, "build_trainer", build_broken)
    run.run_cell(CELLS[0], seed, 0.3, False, rehearsal=rehearsal)
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1])["correct"] is False
    return set(re.findall(r"check (\w+): .* -> FAIL", out))


def _a_block_left_out(monkeypatch):
    """A model whose last block runs and whose output is dropped: its
    parameters are in the tree, as the reference reads it, and the forward
    pass goes round them: in the scoring pass, the train step, ``predict``
    and ``evaluate`` alike."""
    from mercury_tpu.models import TransformerClassifier
    from mercury_tpu.train import trainer as trainer_mod

    class Skips(TransformerClassifier):
        def __call__(self, x, train: bool = True):
            x = self.embed(x)
            for block in self.blocks[:-1]:
                x = block(x)
            self.blocks[-1](x)
            return self.head(x)

    create = trainer_mod.create_model

    def create_skipping(name, **kwargs):
        model = create(name, **kwargs)
        fields = {f: getattr(model, f) for f in model.__dataclass_fields__
                  if f not in ("parent", "name")}
        return Skips(**fields)

    monkeypatch.setattr(trainer_mod, "create_model", create_skipping)


@pytest.mark.parametrize("fault, failing", [
    (_a_block_left_out, {"loss_gap", "weight_gap", "logit_gap",
                         "eval_loss_gap"}),
    (lambda t: _evaluate_drops_rows(t, TRANSFORMER["reference"]),
     {"eval_loss_gap"}),
    (_keeps_params, {"update_norm_gap", "window_update_rms"}),
], ids=["a_block_left_out", "evaluate_drops_rows", "stopped_optimizer"])
def test_a_broken_transformer_is_not_correct(capsys, monkeypatch, fault,
                                             failing):
    """The second family's timed path broken underneath: ``correct:
    false``, each by the numbers meant to catch it. (This family has one
    mode, so an evaluate in training mode is no fault here; an evaluate
    over part of the rows stands in its place.)"""
    if fault is _a_block_left_out:       # breaks the model, not the trainer
        fault, _ = None, fault(monkeypatch)
    failed = _failed_checks_of_a_broken_run(capsys, monkeypatch, fault, 5,
                                            _tiny(TRANSFORMER))
    assert failing <= failed, (failing, failed)


def test_refuses_to_run_off_the_chip():
    """Not told it is a rehearsal: non-zero exit and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


# ------------------------------------------------------------- data files
def test_manifest_meets_the_contract():
    m = MANIFEST
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (m["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in m[k]]
    assert all(NAME.match(n) for n in names)
    for group in ("configs", "workloads"):
        ns = [x["name"] for x in m[group]]
        assert len(ns) == len(set(ns))
    metric_names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for x in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
    for x in m["end_to_end"]:
        assert set(x) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
    assert any(x["name"] == "setup_s" for x in m["end_to_end"])
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(m["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert NAME.match(w["traffic"])
    used = {w["config"] for w in m["workloads"]}
    assert used == {c["name"] for c in m["configs"]}
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(tuple(p + "/" for p in m["paths"]))
    assert len(json.dumps(m)) < 64 * 1024
    for path in m["paths"]:
        for f in glob.glob(os.path.join(REPO, path, "**"), recursive=True):
            rel = os.path.relpath(f, REPO)
            if "__pycache__" in rel or "/_" in rel:
                continue  # git-ignored: caches, captures, the checkout
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_load_and_agree(name):
    c = cell_mod.Cell(name)
    fields = c.train_config_fields(seed=2 ** 31 + 7, trace=False)
    from mercury_tpu import TrainConfig

    config = TrainConfig(**fields)  # every field is one TrainConfig has
    assert config.scan_steps == 1 and config.log_every == 100
    assert config.eval_every == 0 and config.checkpoint_every == 0
    assert config.steps_per_epoch == 1
    assert config.num_epochs * config.steps_per_epoch >= 100_000
    assert config.world_size == c.chips
    assert c.workload["why"] == c.entry["why"]
    assert c.workload["traffic"] == c.entry["traffic"]
    # The traced calls together hold a log gate, and are the window's own.
    assert c.trace_calls * c.steps_per_call >= config.log_every
    # the replay reads the drawn batch from the state
    assert config.pipelined_scoring and config.optimizer == "adam"
    cfg_entry = next(x for x in MANIFEST["configs"]
                     if x["name"] == c.entry["config"])
    assert c.config["reduced"] == cfg_entry["reduced"]
    assert c.config["source"] == cfg_entry["source"]
    for name in ("logit_gap", "eval_loss_gap", "loss_gap", "grad_norm_gap",
                 "update_norm_gap", "weight_gap"):
        assert c.config["check"][f"{name}_limit"] > 0
    assert c.config["check"]["window_update_rms_floor"] > 0
    e2e = {m["name"] for m in c.end_to_end()}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = c.per_layer()
    assert layer
    for m in layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])


@pytest.mark.parametrize("name",
                         [m["name"] for m in MANIFEST["per_layer"]])
def test_layer_metric_files(name):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    spec = cell_mod.layer_metric(name)
    for key in ("layer", "unit", "better", "source", "moves"):
        assert spec[key] == entry[key], key
    assert entry["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert callable(cell_mod.reducer(spec["reducer"]))
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    assert entry["moves"] in e2e


# -------------------------------------------------------------- reference
def _tiny_resnet(block, compute_dtype):
    from mercury_tpu.models import BasicBlock, Bottleneck, ResNet

    return ResNet(stage_sizes=[1, 2, 1, 1],
                  block_cls={"basic": BasicBlock,
                             "bottleneck": Bottleneck}[block],
                  num_classes=10, num_filters=8, compute_dtype=compute_dtype)


def _seeded_variables(model, seed=0):
    """Seeded weights with BatchNorm statistics and affine terms moved
    off their 0/1 initial values, so every term of the equations counts."""
    x = jnp.zeros((1, 32, 32, 3))
    variables = model.init(jax.random.key(seed), x, train=False)
    leaves, tree = jax.tree.flatten_with_path(variables)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    out = []
    for (path, leaf), k in zip(leaves, keys):
        last = jax.tree_util.keystr(path)
        if "var" in last or "scale" in last:
            leaf = jax.random.uniform(k, leaf.shape, minval=0.5, maxval=1.5)
        elif "mean" in last or "bias" in last:
            leaf = 0.2 * jax.random.normal(k, leaf.shape)
        out.append(leaf)
    return jax.tree.unflatten(jax.tree.structure(variables), out)


@pytest.mark.parametrize("block", ["basic", "bottleneck"])
def test_reference_follows_the_models_equations(block):
    """``reference.py`` against ``models/resnet.py`` in float32 at tiny
    size: the same equations, two implementations."""
    model = _tiny_resnet(block, jnp.float32)
    v = _seeded_variables(model)
    x = jax.random.normal(jax.random.key(9), (4, 32, 32, 3))
    with jax.default_matmul_precision("highest"):
        want = model.apply(v, x, train=False)
    got = RESNET.resnet_forward(v["params"], v["batch_stats"], x,
                                [1, 2, 1, 1], block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("block", ["basic", "bottleneck"])
def test_check_a_passes_as_stated_and_fails_a_precision_lower(block):
    """The inference check's number at tiny size: the system side in bfloat16 compute
    (what the configurations state) and in float32 lies well under the
    control's — the same forward with inputs and weights of every
    convolution rounded to fp8 — and a limit between the two passes the
    one and fails the other."""
    arch = {"file": RESNET_FILE, "stage_sizes": [1, 2, 1, 1], "block": block,
            "mean": [0.5, 0.5, 0.5], "std": [0.25, 0.25, 0.25]}
    v = _seeded_variables(_tiny_resnet(block, jnp.float32), seed=3)
    images = np.asarray(jax.random.randint(
        jax.random.key(4), (64, 32, 32, 3), 0, 256), np.uint8)
    ref = reference.outputs(v["params"], v["batch_stats"], images, arch)
    x = RESNET.prepare(jnp.asarray(images), arch)
    gaps = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        system = _tiny_resnet(block, dtype).apply(v, x, train=False)
        gaps[dtype] = check.logit_gap(system, ref)
    control = check.logit_gap(
        reference.outputs(v["params"], v["batch_stats"], images, arch,
                          quantize="fp8"), ref)
    assert gaps[jnp.float32] < 1e-4
    assert 3 * gaps[jnp.bfloat16] < control, (gaps, control)
    limit = (gaps[jnp.bfloat16] * control) ** 0.5
    assert check.Number("logit_gap", gaps[jnp.bfloat16], limit).ok
    assert not check.Number("logit_gap", control, limit).ok


def test_a_number_that_is_not_finite_still_makes_a_json_line():
    entry = check.Number("weight_gap", float("inf"), 0.1).entry()
    assert entry == {"value": "inf", "rule": "<=", "limit": 0.1, "ok": False}
    assert json.loads(json.dumps(entry, allow_nan=False)) == entry
    assert check.Number("loss_gap", np.float32(0.5), 1.0).entry()["value"] \
        == 0.5


def test_numbers_fail_one_by_one():
    replayed = {"loss_gap": 0.001, "grad_norm_gap": 0.001,
                "update_norm_gap": 0.001, "weight_gap": 0.001}
    good = dict(system_outputs=np.ones((4, 3)), ref_outputs=np.ones((4, 3)),
                eval_loss=0.02, ref_eval_loss=0.02, replay=replayed,
                window_update_rms=1e-3, window_losses=[0.5, 0.4],
                steps_counted=200, steps_advanced=200, compiles=0)
    limits = {"logit_gap_limit": 0.01, "eval_loss_gap_limit": 0.01,
              "loss_gap_limit": 0.01, "grad_norm_gap_limit": 0.01,
              "update_norm_gap_limit": 0.01, "weight_gap_limit": 0.01,
              "window_update_rms_floor": 1e-4}
    assert all(n.ok for n in check.numbers(limits, **good))
    for change, failing in [
            (dict(system_outputs=np.full((4, 3), 1.1)), "logit_gap"),
            # relative, however small the loss: 0.0205 against 0.02
            (dict(eval_loss=0.0205), "eval_loss_gap"),
            (dict(window_losses=[0.5, float("nan")]), "nonfinite_losses"),
            (dict(steps_advanced=100), "steps_advanced"),
            (dict(window_update_rms=0.0), "window_update_rms"),
            (dict(compiles=1), "compiles_in_window")] + [
            (dict(replay=dict(replayed, **{name: 0.02})), name)
            for name in replayed] + [
            (dict(replay=dict(replayed, weight_gap=float("inf"))),
             "weight_gap")]:
        bad = [n.name for n in check.numbers(limits, **dict(good, **change))
               if not n.ok]
        assert bad == [failing], (change, bad)
    assert check.failed_steps([0.1, float("inf"), 0.2], 100) == 100
    idx = check.sample_indices(2 ** 31 + 9, 1000)
    assert len(set(idx)) == 256 and (idx == check.sample_indices(
        2 ** 31 + 9, 1000)).all()


def _program_steps(model, variables, arch, fields, seed):
    """What a ``replay.Recorder`` would keep of STEPS + 1 steps of a plain
    flax + optax Mercury train step (reweighted loss, Adam under the cosine
    schedule) in the model's compute dtype: the program's side of the
    replay at a size a test can hold."""
    import optax
    from mercury_tpu.train.state import PendingBatch, make_optimizer

    tx = make_optimizer("adam", fields["base_lr"],
                        fields["steps_per_epoch"] * fields["num_epochs"])
    params, opt_state = variables["params"], None
    opt_state = tx.init(params)
    keys = jax.random.split(jax.random.key(seed), replay.STEPS + 2)

    def batch(key):
        k1, k2, k3 = jax.random.split(key, 3)
        n = fields["batch_size"]
        return PendingBatch(
            images=jax.random.normal(k1, (1, n, 32, 32, 3)),
            labels=jax.random.randint(k2, (1, n), 0, 10),
            scaled_probs=jax.random.uniform(k3, (1, n), minval=0.5,
                                            maxval=2.0))

    def loss_fn(p, b):
        z, _ = model.apply({"params": p,
                            "batch_stats": variables["batch_stats"]},
                           b.images[0], train=True, mutable=["batch_stats"])
        return jnp.mean(RESNET.example_loss(
            z.astype(jnp.float32), b.labels[0]) / b.scaled_probs[0])

    steps, pending = [], batch(keys[0])
    for i in range(replay.STEPS + 1):
        loss, grads = jax.value_and_grad(loss_fn)(params, pending)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        pending = batch(keys[i + 1])
        adam = replay._adam_state(opt_state)
        steps.append(dict(
            metrics={"train/loss": float(loss)}, mu=replay._host(adam.mu),
            nu=replay._host(adam.nu), count=int(adam.count),
            pending=replay._host(pending), params=replay._host(params)))
    return steps


@pytest.mark.parametrize("block", ["basic", "bottleneck"])
def test_replay_passes_as_stated_and_fails_a_precision_lower(block):
    """The replay at tiny size. A train step in float32 agrees with the
    reference; in bfloat16 compute (what the configurations state) its
    loss, gradient norm and update norm stay far under what a fault
    reads (half a batch: 0.3-0.5; a state unchanged: 1). The number the
    lower precision fails is the pool's: ``N p_i`` from a bfloat16 scoring
    forward lies well under the control's (the reference with every
    convolution's inputs and weights rounded to fp8), and a limit between
    the two passes the one and fails the other."""
    arch = {"file": RESNET_FILE, "stage_sizes": [1, 2, 1, 1], "block": block,
            "adam": {"b1": 0.9, "b2": 0.999, "eps": 1e-8}}
    fields = {"world_size": 1, "base_lr": 0.001, "steps_per_epoch": 1,
              "num_epochs": 1000, "batch_size": 16}
    v = _seeded_variables(_tiny_resnet(block, jnp.float32), seed=5)
    gaps = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        steps = _program_steps(_tiny_resnet(block, dtype), v, arch, fields,
                               seed=6)
        gaps[dtype] = replay.step_gaps(
            replay.system_steps(steps, arch),
            replay.reference_steps(steps, arch, fields))
    assert max(gaps[jnp.float32].values()) < 0.03, gaps
    assert gaps[jnp.bfloat16]["loss_gap"] < 0.01, gaps
    assert gaps[jnp.bfloat16]["grad_norm_gap"] < 0.1, gaps
    assert gaps[jnp.bfloat16]["update_norm_gap"] < 0.25, gaps

    sampling = {"is_alpha": 0.5, "ema_alpha": 0.9}
    x = jax.random.normal(jax.random.key(7), (160, 32, 32, 3))
    y = jax.random.randint(jax.random.key(8), (160,), 0, 10)

    def weights(logits):
        return reference.scaled_probs(
            RESNET.example_loss(logits, y), 0.0, 0, sampling)

    program, _ = _tiny_resnet(block, jnp.bfloat16).apply(
        v, x, train=True, mutable=["batch_stats"])
    ref = weights(RESNET.forward(v["params"], None, x, arch))
    sound = replay.weight_gap(weights(program), ref)
    control = replay.weight_gap(
        weights(RESNET.forward(v["params"], None, x, arch, "fp8")), ref)
    assert 2 * sound < control, (sound, control)
    limit = (sound * control) ** 0.5
    assert check.Number("weight_gap", sound, limit).ok
    assert not check.Number("weight_gap", control, limit).ok


def test_a_metric_reports_only_in_the_cells_it_lists(monkeypatch):
    """A manifest entry's ``workloads`` key keeps a metric out of the
    other cells' result lines (a later PR's cell-specific metric)."""
    m = json.loads(json.dumps(MANIFEST))
    m["per_layer"].append(dict(m["per_layer"][0], name="elsewhere_only",
                               workloads=["another-cell"]))
    m["per_layer"].append(dict(m["per_layer"][0], name="here_too",
                               workloads=[CELLS[0]]))
    monkeypatch.setattr(cell_mod, "manifest", lambda: m)
    names = {x["name"] for x in cell_mod.Cell(CELLS[0]).per_layer()}
    assert "here_too" in names and "elsewhere_only" not in names


# ------------------------------------------------------------------ FLOPs
CONFIG_FILES = sorted(glob.glob(os.path.join(REPO, "perfbench", "configs",
                                             "*.json")))


@pytest.mark.parametrize("config", CONFIG_FILES,
                         ids=lambda p: os.path.basename(p)[:-5])
def test_fwd_flops_per_example(config):
    """The config file's constant is what its own family's file counts for
    it (``fwd_flops_per_example(config)``: the operations the model
    requires for one example's forward pass)."""
    cfg = json.load(open(config))
    want = reference.family(cfg["reference"]).fwd_flops_per_example(cfg)
    assert want > 0 and cfg["fwd_flops_per_example"] == want


@pytest.mark.parametrize(
    "config", [p for p in CONFIG_FILES if json.load(open(p))["reference"]
               ["file"] == RESNET_FILE],
    ids=lambda p: os.path.basename(p)[:-5])
def test_resnet_flops_are_xlas_without_the_padding_taps(config):
    """The ResNet family's count is the conventional one (2 x MACs of
    every conv and the head, padding taps included: 1.11 GFLOP for
    ResNet-18, 2.60 for ResNet-50 at 32x32); without the padding taps it
    is XLA's own count of the plain reference's forward within 2 %."""
    cfg = json.load(open(config))
    arch = cfg["reference"]
    want = RESNET.fwd_flops_per_example(cfg)
    from mercury_tpu.models import create_model

    model = create_model(cfg["train_config"]["model"],
                         num_classes=cfg["num_classes"],
                         compute_dtype="float32")
    shape = (1, cfg["image_size"], cfg["image_size"], 3)
    v = jax.eval_shape(lambda: model.init(jax.random.key(0),
                                          jnp.zeros(shape), train=False))
    cost = jax.jit(lambda p, s, x: RESNET.forward(p, s, x, arch)).lower(
        v["params"], v["batch_stats"],
        jax.ShapeDtypeStruct(shape, jnp.float32)).cost_analysis()
    exact = RESNET.resnet_forward_flops(arch, cfg["image_size"],
                                        cfg["num_classes"], skip_padding=True)
    assert abs(cost["flops"] / exact - 1.0) < 0.02
    assert 0.8 * want < exact < want


# -------------------------------------------------------- trace reduction
def test_trace_reduce_is_the_programs_reduction():
    from mercury_tpu.obs import profile_parse

    assert (trace_reduce.parse_profile(FIXTURE)
            == profile_parse.parse_profile(FIXTURE))


def test_capture_cuts_to_the_step_module():
    events, _ = trace_reduce.load_events(FIXTURE)
    cap = trace_reduce.Capture(events, "jit_fused_train_step")
    assert len(cap.planes) == 1 and cap.step_count() == 3
    whole = trace_reduce.parse_profile(FIXTURE)
    # the fixture holds step programs only, so the cut changes nothing
    assert cap.step_device_us() == pytest.approx(
        whole["total_device_time_us"])
    assert cap.scope_share("mercury_scoring") == pytest.approx(
        whole["scopes"]["mercury_scoring"]["frac"])
    idle = cap.step_idle()
    assert idle["busy_us"] == pytest.approx(whole["idle"]["busy_us"])
    assert 0 < idle["idle_frac"] < 1
    kinds = {w for w, _ in idle["gaps"]}
    assert kinds == {"inside_step_program", "between_step_programs"}
    assert cap.whole_busy_us() == pytest.approx(whole["idle"]["busy_us"])
    top = cap.top_ops(10)
    assert top and top[0][1] >= top[-1][1] > 0 and len(top) <= 10
    assert len(cap.gap_summary(10)) <= 10
    # a capture that names no such module: everything counts as the step
    assert trace_reduce.Capture(events, "jit_absent").step_count() == 0


def test_a_capture_that_lost_step_programs():
    """The first traced run on a just-compiled step loses step programs
    from its capture (PERF.md, PR 26: 93 and 115 of 120). Cut the middle
    one of the fixture's three out, with its ops: the per-step readings
    divide by the two it holds and stand where the whole capture's do, and
    the run says how many were lost."""
    events, _ = trace_reduce.load_events(FIXTURE)
    whole = trace_reduce.Capture(events, "jit_fused_train_step")
    lo, hi = whole.planes[0]["steps"][1]
    cut = trace_reduce.Capture(
        [e for e in events if not (e.get("ph") == "X" and e.get("pid") == 1
                                   and lo <= float(e["ts"]) < hi)],
        "jit_fused_train_step")
    assert whole.step_count() == 3 and cut.step_count() == 2
    assert whole.steps_held(3) == 3 and cut.steps_held(3) == 2
    assert whole.lost_step_us(3) == 0.0
    assert cut.lost_step_us(3) == pytest.approx(hi - lo)
    # a capture that names no such module counts every op as the step's
    assert trace_reduce.Capture(events, "jit_absent").steps_held(3) == 3
    readings = {}
    for name, capture in (("whole", whole), ("cut", cut)):
        ctx = dict(capture=capture, steps=3, step_flops=1e9, peak_flops=1e12)
        readings[name] = {r: cell_mod.reducer(r)(ctx) for r in (
            "device_ms_per_step", "step_roofline_share", "device_idle_share")}
    for r in ("device_ms_per_step", "step_roofline_share"):
        assert readings["cut"][r] == pytest.approx(readings["whole"][r],
                                                   rel=0.01), r
    # by the steps asked for it would read a third low
    assert cut.step_device_us() / 3e3 < 0.7 * readings["whole"][
        "device_ms_per_step"]
    assert readings["cut"]["device_idle_share"] == pytest.approx(
        readings["whole"]["device_idle_share"], abs=3.0)
    assert 100.0 * cut.step_idle()["idle_frac"] > 45.0  # uncorrected
    assert run.lost_steps_note(whole, 3) is None
    assert "lost 1 of 3 step programs" in run.lost_steps_note(cut, 3)


@pytest.mark.parametrize("name, args, want", [
    ("span_ms_per_step", {"span": "trainer/log_gate"}, 0.03),
    ("count", {"counter": "compiles_in_window"}, 0.0),
])
def test_host_side_reducers(name, args, want):
    ctx = dict(call_wall_s=4.0, steps=100,
               counters={"compiles_in_window": 0},
               spans=[{"name": "trainer/log_gate", "ph": "X", "dur": 3000.0},
                      {"name": "trainer/dispatch", "ph": "X", "dur": 9.0}])
    assert cell_mod.reducer(name)(ctx, **args) == pytest.approx(want)


def test_trace_reducers_on_the_fixture():
    events, _ = trace_reduce.load_events(FIXTURE)
    cap = trace_reduce.Capture(events, "jit_fused_train_step")
    ctx = dict(capture=cap, steps=3, step_flops=1e9, peak_flops=1e12)
    ms = cell_mod.reducer("device_ms_per_step")(ctx)
    assert ms == pytest.approx(cap.step_device_us() / 3e3)
    assert cell_mod.reducer("step_roofline_share")(ctx) == pytest.approx(
        100.0 * 1e9 / (ms / 1e3) / 1e12)
    assert 0 < cell_mod.reducer("device_idle_share")(ctx) < 100
    assert cell_mod.reducer("scope_share")(
        ctx, scope="mercury_scoring") == pytest.approx(
            100.0 * cap.scope_share("mercury_scoring"))
    # every op of the fixture runs inside the one program it names
    ctx["programs"] = {"evaluate": "jit_fused_train_step"}
    assert cell_mod.reducer("program_share")(
        ctx, program="evaluate") == pytest.approx(100.0)
    ctx["programs"] = {"evaluate": "jit_absent"}
    assert cell_mod.reducer("program_share")(ctx, program="evaluate") is None
    empty = dict(ctx, capture=trace_reduce.Capture([], "x"))
    for name in ("device_ms_per_step", "step_roofline_share",
                 "device_idle_share"):
        assert cell_mod.reducer(name)(empty) is None  # nothing to read
