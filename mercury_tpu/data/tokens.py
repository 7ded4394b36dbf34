"""Token datasets: ``int32 [N, T]`` inputs with the labels shifted by one.

The sealed machines hold no corpus, so ``tokens_zipf`` is a seeded stand-in,
as CIFAR's is (``data/cifar.py::synthetic_cifar``): sequences of
``seq_len + 1`` ids drawn from the vocabulary slice ``[0, vocab)`` under a
Zipf law (a few ids take most positions, as in text). Half of the sequences
are iid draws, whose loss cannot fall below the entropy of the law; the
other half repeat a ``pattern``-token draw, which a model with attention
learns to copy: per-sequence losses differ once training starts, which is
what an importance sampler needs to have something to choose between.
Inputs are ids ``[0:seq_len]``, labels ids ``[1:seq_len + 1]``.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

#: Vocabulary rows of ``tokens_zipf`` where the config names none: an
#: eighth of a 151,936-row vocabulary.
DEFAULT_VOCAB = 18_992
TOKEN_DATASETS = ("tokens_zipf",)

Split = Tuple[np.ndarray, np.ndarray]


def zipf_tokens(vocab: int, seq_len: int, train_size: int = 512,
                test_size: int = 8, seed: int = 0, exponent: float = 1.1,
                pattern: int = 64) -> Tuple[Split, Split]:
    """``((x_train, y_train), (x_test, y_test))``, each ``int32 [N, T]``;
    odd rows repeat a ``pattern``-token draw, even rows are iid."""
    rng = np.random.default_rng([int(seed), 0x70C])
    cdf = np.cumsum(np.arange(1, vocab + 1, dtype=np.float64) ** -exponent)
    cdf /= cdf[-1]

    def draw(shape):
        ids = np.searchsorted(cdf, rng.random(shape), side="right")
        return np.minimum(ids, vocab - 1).astype(np.int32)

    n, length = train_size + test_size, seq_len + 1
    ids = draw((n, length))
    motif = draw((n, min(pattern, length)))
    tiled = np.tile(motif, (1, -(-length // motif.shape[1])))[:, :length]
    ids[1::2] = tiled[1::2]
    train, test = ids[:train_size], ids[train_size:]
    return ((train[:, :-1], train[:, 1:]), (test[:, :-1], test[:, 1:]))


def load_token_dataset(name: str, vocab: int, seq_len: int,
                       seed: int = 0) -> Tuple[Split, Split, Dict]:
    """``train, test, info`` as ``data.cifar.load_dataset`` returns them;
    ``num_classes`` is the vocabulary slice, the statistics are the
    identity (token rows are not normalised)."""
    if name != "tokens_zipf":
        raise ValueError(f"unknown token dataset {name!r}")
    train, test = zipf_tokens(vocab, seq_len, seed=seed)
    return train, test, {"num_classes": int(vocab), "mean": np.zeros(1),
                         "std": np.ones(1), "synthetic": True}
