"""A workload's ``weights_seed``: one model, other documents.

The program draws its weights, its stream and its data from the one
``TrainConfig.seed``; on a routed model the weights decide how many pairs
fall on the held experts, and with them the rate's level (PERF.md section 6,
PR 48: one seed twice agreed to 0.007 %, seeds lay 1.9-3 % apart). A token
cell's file therefore names the seed of its weights, and ``--seed`` draws
the data, through the program's own ``Trainer(config, dataset=...)``."""

import os
import sys

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
for path in (REPO, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

from perfbench import run  # noqa: E402
from perfbench.cell import Cell  # noqa: E402
from test_host_room import ROOM  # noqa: E402

TOKEN_CELLS = ["st21b-is-8k", "kn2-is-8k"]


@pytest.mark.parametrize("name", TOKEN_CELLS)
def test_a_token_cell_trains_one_set_of_weights_on_every_seeds_data(name):
    cell = Cell(name)
    weights = cell.workload["weights_seed"]
    assert 0 <= weights < 2 ** 31 - 1
    for seed in (3, 2 ** 31 + 7):
        assert cell.train_config_fields(seed, False)["seed"] == weights
        assert cell.data_seed(seed) == seed % (2 ** 31 - 1)


def test_a_cell_without_the_key_hands_the_program_its_one_seed():
    cell = Cell("r50c100-is")
    assert "weights_seed" not in cell.workload
    assert cell.data_seed(2 ** 31 + 7) is None
    assert cell.train_config_fields(2 ** 31 + 7, False)["seed"] == 8


def test_the_data_is_the_seeds_and_the_weights_are_the_files():
    """Two seeds: the same parameters leaf for leaf, other rows; and the
    rows are those ``TrainConfig(seed=data_seed)`` would have trained on."""
    from mercury_tpu import TrainConfig
    from mercury_tpu.train import build_dataset

    cell = Cell("st21b-is-8k", rehearsal=ROOM)
    built = {}
    for seed in (11, 12):
        fields = cell.train_config_fields(seed, False)
        trainer = run.build_trainer(fields, cell.data_seed(seed))
        try:
            built[seed] = (
                jax.tree.map(np.array, jax.device_get(trainer.state.params)),
                np.array(trainer.dataset.x_train),
                np.array(trainer.dataset.x_test))
        finally:
            trainer.close()
    (p11, x11, t11), (p12, x12, t12) = built[11], built[12]
    for a, b in zip(jax.tree.leaves(p11), jax.tree.leaves(p12)):
        np.testing.assert_array_equal(a, b)
    assert not np.array_equal(x11, x12) and not np.array_equal(t11, t12)
    own = build_dataset(TrainConfig(**dict(fields, seed=12)))
    np.testing.assert_array_equal(x12, np.asarray(own.x_train))
    np.testing.assert_array_equal(t12, np.asarray(own.x_test))
