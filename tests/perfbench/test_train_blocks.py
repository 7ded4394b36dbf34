"""The training side of the plain reference by row blocks: a family says
that its rows are independent (``ROWS_INDEPENDENT = True``), a
configuration says how many rows a block holds (``check.train_block_rows``),
and the pool is then scored and the batch differentiated a block at a time.
Without the key the whole pool and the whole batch go through one forward
each, whatever the family declares."""

import os

import jax
import numpy as np
import pytest

from perfbench import check, reference, replay, run
from test_families import REPO, TOKENS, _spy_on_forward, _token_data
from test_perfbench import (CELLS, TINY, TRANSFORMER, _evaluate_drops_rows,
                            _failed_checks_of_a_broken_run, _half_batch,
                            _keeps_params, _tiny)

#: The second family's rehearsal with the training side in blocks of 3:
#: the batch of 8 is 3 + 3 + 2 rows, the pool of 32 ten blocks and 2.
BLOCKED = dict(TRANSFORMER,
               check=dict(TRANSFORMER["check"], train_block_rows=3))


def _score(params, x, y, arch, pool=10, **kwargs):
    return reference.score_pool(
        params, jax.random.key(5), np.random.default_rng(2).permutation(
            len(x)), 7, 0.0, 0, x, y, np.arange(len(x)), arch, pool, **kwargs)


@pytest.fixture
def undeclared(tmp_path):
    """The token fixture's family without the declaration."""
    source = open(os.path.join(REPO, TOKENS["file"])).read()
    assert "\nROWS_INDEPENDENT = True\n" in source
    path = tmp_path / "coupled_rows.py"
    path.write_text(source.replace("\nROWS_INDEPENDENT = True\n", "\n"))
    return dict(TOKENS, file=str(path))


# ---------------------------------------------------------- (a) score_pool
def test_the_pool_is_scored_a_block_at_a_time(monkeypatch):
    params, x, y = _token_data(n=40)
    whole = _score(params, x, y, TOKENS)
    seen = _spy_on_forward(monkeypatch, TOKENS)
    inputs, labels, losses, scaled = _score(params, x, y, TOKENS,
                                            block_rows=3)
    assert sorted(set(seen)) == [1, 3]      # 10 = 3 x 3 + 1: the ragged one
    assert losses.dtype == np.float64 and losses.shape == (10,)
    assert (inputs == whole[0]).all() and (labels == whole[1]).all()
    np.testing.assert_allclose(losses, whole[2], rtol=1e-6)
    np.testing.assert_allclose(scaled, whole[3], rtol=1e-6)
    # the first block's losses are those of the same rows scored alone
    fam = reference.family(TOKENS)
    alone = fam.example_loss(fam.forward(params, None, inputs[:3], TOKENS),
                             labels[:3])
    np.testing.assert_allclose(losses[:3], np.asarray(alone), rtol=1e-6)


# ------------------------------------------------------- (b) loss_and_grad
@pytest.mark.parametrize("quantize", [None, "fp8"], ids=["stated", "control"])
def test_the_batch_is_differentiated_a_block_at_a_time(monkeypatch, quantize):
    params, x, y = _token_data(n=7)
    weights = np.random.default_rng(3).uniform(0.5, 2.0, 7).astype(np.float32)
    loss, grads = reference.make_loss_and_grad(TOKENS, quantize)(
        params, x, y, weights)
    seen = _spy_on_forward(monkeypatch, TOKENS)
    blocked = reference.make_loss_and_grad(TOKENS, quantize, block_rows=3)
    got_loss, got = blocked(params, x, y, weights)
    assert sorted(set(seen)) == [1, 3]      # 7 = 3 + 3 + 1
    assert jax.tree.structure(got) == jax.tree.structure(grads)
    if quantize:
        # the control's per-tensor scale spans a block: its own reading
        assert float(got_loss) == pytest.approx(float(loss), rel=0.05)
        return
    assert float(got_loss) == pytest.approx(float(loss), rel=1e-5)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(grads)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == np.float32
        assert np.abs(a - b).max() <= 1e-5 * np.abs(b).max()
    # a second call starts from zero again
    again, _ = blocked(params, x, y, weights)
    assert float(again) == float(got_loss)


def test_the_replay_passes_the_size_on(monkeypatch):
    """``replay.compare`` with ``train_block_rows``: the hand-made token
    steps read as they do whole, and no forward holds more than a block."""
    from test_families import _token_steps

    fields = {"world_size": 1, "base_lr": 0.01, "steps_per_epoch": 1,
              "num_epochs": 1000, "batch_size": 4, "presample_batches": 3}
    params, x, y = _token_data(n=40)
    steps = _token_steps(params, x, y, fields)
    for kept in steps[2:]:          # what the recorder no longer keeps
        del kept["mu"]
    dataset = (x, y, np.arange(len(x))[None])
    system = replay.system_steps(steps, TOKENS)
    # the reference consumes the start's moments: one copy of the steps each
    whole = replay.compare(system, [dict(s) for s in steps], dataset, TOKENS,
                           fields)
    seen = _spy_on_forward(monkeypatch, TOKENS)
    gaps = replay.compare(system, steps, dataset, TOKENS, fields,
                          train_block_rows=3)
    assert max(seen) == 3 and 1 in seen     # batch 4 = 3 + 1, pool 12
    assert gaps["weight_gap"] == pytest.approx(0.0, abs=1e-6)
    for name, value in whole.items():
        assert gaps[name] == pytest.approx(value, abs=1e-5), name


# ------------------------------------------------- (c) without the key
@pytest.mark.parametrize("declared", [True, False])
def test_without_the_key_one_forward_takes_the_whole(monkeypatch, declared,
                                                     undeclared):
    arch = TOKENS if declared else undeclared
    assert reference.rows_independent(arch) is declared
    assert check.train_block_rows({}, declared) is None
    params, x, y = _token_data(n=40)
    seen = _spy_on_forward(monkeypatch, arch)
    _score(params, x, y, arch)
    reference.make_loss_and_grad(arch)(params, x[:7], y[:7],
                                       np.ones(7, np.float32))
    assert seen == [10, 7]


# ------------------------------------ (d) a family of coupled rows and the key
def test_coupled_rows_take_no_blocks(undeclared):
    assert check.train_block_rows({"train_block_rows": 4}, True) == 4
    with pytest.raises(SystemExit, match="does not declare ROWS_INDEPENDENT"):
        check.train_block_rows({"train_block_rows": 4}, False)
    with pytest.raises(SystemExit, match="want 1 or more"):
        check.train_block_rows({"train_block_rows": 0}, True)
    params, x, y = _token_data(n=40)
    with pytest.raises(ValueError, match="does not declare ROWS_INDEPENDENT"):
        _score(params, x, y, undeclared, block_rows=3)
    with pytest.raises(ValueError, match="does not declare ROWS_INDEPENDENT"):
        reference.make_loss_and_grad(undeclared, block_rows=3)


@pytest.mark.parametrize("path", ["perfbench/references/smallcnn.py",
                                  "perfbench/references/resnet.py"],
                         ids=lambda p: os.path.basename(p)[:-3])
def test_the_run_stops_before_any_step(monkeypatch, path):
    """BatchNorm's batch statistic couples the rows of a pool: neither
    file declares independence, and a configuration that sizes blocks for
    one stops the run before the trainer is built."""
    assert not reference.rows_independent({"file": path})
    monkeypatch.setattr(run, "build_trainer",
                        lambda fields: pytest.fail("the trainer was built"))
    rehearsal = dict(TINY, check=dict(TINY["check"], train_block_rows=4),
                     reference=dict(TINY["reference"], file=path))
    with pytest.raises(SystemExit, match="does not declare ROWS_INDEPENDENT "
                                         "= True"):
        run.run_cell(CELLS[0], 3, 0.3, False, rehearsal=rehearsal)


# --------------------------------------------------------- (e) the loader
@pytest.mark.parametrize("value", ["1", "'yes'", "None"])
def test_the_declaration_is_a_bool(tmp_path, value):
    source = open(os.path.join(REPO, TOKENS["file"])).read()
    path = tmp_path / "declares.py"
    path.write_text(source.replace("ROWS_INDEPENDENT = True",
                                   f"ROWS_INDEPENDENT = {value}"))
    with pytest.raises(TypeError, match="ROWS_INDEPENDENT is .*want True or "
                                        "False"):
        reference.family({"file": str(path)})


def test_which_families_declare_it():
    declared = {os.path.basename(p)[:-3] for p in (
        "perfbench/references/resnet.py", "perfbench/references/smallcnn.py",
        "perfbench/references/transformer_classifier.py", TOKENS["file"])
        if reference.rows_independent({"file": p})}
    assert declared == {"transformer_classifier", "token_family"}


# -------------------------------------------- (f) the whole command, blocked
def test_whole_command_with_the_training_side_in_blocks(capsys, monkeypatch):
    """The rehearsal at ``transformer_classifier`` size with
    ``train_block_rows`` 3: ``correct`` as stated, no training-mode forward
    of the reference over more than a block, and the lower-precision
    control over the limits the program passes."""
    seen = _spy_on_forward(monkeypatch, BLOCKED["reference"])
    result = run.run_cell(CELLS[0], 2 ** 31 + 17, 0.3, False,
                          rehearsal=_tiny(BLOCKED), control=True)
    out = capsys.readouterr().out
    assert result["correct"] is True
    assert "8 of 8 drawn rows found in the rebuilt pool of 32" in out
    # training mode by blocks (3, ragged 2); the sample of 32 rows and the
    # evaluate side by ``block_rows`` (100)
    assert {2, 3} <= set(seen) <= {2, 3, 32, 100}
    limits = BLOCKED["check"]
    for name in ("logit_gap", "weight_gap"):
        limit = limits[f"{name}_limit"]
        assert check.Number(name, result["numbers"][name], limit).ok
        assert not check.Number(name, result["control"][name], limit).ok


@pytest.mark.parametrize("fault, failing", [
    (_half_batch, {"loss_gap", "grad_norm_gap"}),
    (_keeps_params, {"update_norm_gap", "window_update_rms"}),
    (lambda t: _evaluate_drops_rows(t, TRANSFORMER["reference"]),
     {"eval_loss_gap"}),
], ids=["half_batch", "stopped_optimizer", "evaluate_drops_rows"])
def test_a_broken_path_is_not_correct_with_blocks_on(capsys, monkeypatch,
                                                     fault, failing):
    failed = _failed_checks_of_a_broken_run(capsys, monkeypatch, fault, 5,
                                            _tiny(BLOCKED))
    assert failing <= failed, (failing, failed)
