"""On-device data pipeline: index-carrying batches, jit'd augmentation, and
per-worker presampling streams.

Replaces the reference's loader stack — ``get_dataloader_CIFAR10``
(``cifar10/data_loader.py:177-211``), the index-carrying datasets
(``cifar10/datasets.py:39-96``, ``util.py:240-273``), the wrapping
presampling iterator ``Trainer.get_next`` (``pytorch_collab.py:74-82``) and
the transforms ``_data_transforms_cifar10``
(``cifar10/data_loader.py:79-109``) — with a TPU-first design: the whole
dataset lives in device memory as arrays; "loading" a batch is a gather by
index inside the jitted step; augmentation is pure ``jax.random`` ops fused
into the same XLA program. No host↔device transfer per step.

The index-carrying contract (``(index, image, target)``,
``cifar10/datasets.py:93``) becomes the :class:`Batch` NamedTuple whose
``index`` field travels with every batch so importance scores attribute to
global sample ids.
"""

from __future__ import annotations

import dataclasses
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class Batch(NamedTuple):
    """Index-carrying batch (mirror of the ``(index, img, target)`` tuple
    contract, ``cifar10/datasets.py:77-93``)."""

    index: jax.Array  # [B] int32 — global sample ids
    image: jax.Array  # [B, H, W, C] float
    label: jax.Array  # [B] int32


class ShardStream(NamedTuple):
    """Carried jit state for one worker's wrapping, shuffled presampling
    stream (functional replacement of ``Trainer.get_next``'s infinite
    iterator, ``pytorch_collab.py:74-82``)."""

    perm: jax.Array    # [L] int32 — current epoch permutation of shard slots
    cursor: jax.Array  # [] int32 — next unread slot


def normalize_images(images: jax.Array, mean: np.ndarray, std: np.ndarray) -> jax.Array:
    """uint8 NHWC → normalized float (``cifar10/data_loader.py:83-96``:
    ``ToTensor`` + ``Normalize(mean, std)``). Float inputs (e.g. feature
    sequences ``[N, T, F]``) skip the /255 scaling; mean/std broadcast over
    the trailing axis."""
    if images.dtype == jnp.uint8:
        x = images.astype(jnp.float32) / 255.0
    else:
        x = images.astype(jnp.float32)
    return (x - jnp.asarray(mean)) / jnp.asarray(std)


def _take_crops(images: jax.Array, oy: jax.Array, ox: jax.Array, out_h: int, out_w: int) -> jax.Array:
    """Crop every image ``i`` of ``[N, H, W, C]`` at its own offset
    ``(oy[i], ox[i])`` with two batched ``take_along_axis`` gathers — the
    whole batch crops in two vectorized HBM reads instead of N per-image
    dynamic slices (which lower to N serialized gathers on TPU)."""
    idx_y = oy[:, None] + jnp.arange(out_h)[None, :]              # [N, out_h]
    idx_x = ox[:, None] + jnp.arange(out_w)[None, :]              # [N, out_w]
    rows = jnp.take_along_axis(images, idx_y[:, :, None, None], axis=1)
    return jnp.take_along_axis(rows, idx_x[:, None, :, None], axis=2)


def random_crop_batch(key: jax.Array, images: jax.Array, pad: int) -> jax.Array:
    """Zero-pad by ``pad`` then crop back to the original size at a random
    per-image offset (``transforms.RandomCrop(32, padding=4)``,
    ``cifar10/data_loader.py:85``), fully batched."""
    n, h, w, _ = images.shape
    padded = jnp.pad(images, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    off = jax.random.randint(key, (n, 2), 0, 2 * pad + 1)
    return _take_crops(padded, off[:, 0], off[:, 1], h, w)


def random_crop_to_batch(key: jax.Array, images: jax.Array, out: int) -> jax.Array:
    """Random crop of ``[N, H, W, C]`` down to ``out×out`` with no padding
    (the IID path crops a larger resized image, ``exp_dataset.py:26-27``)."""
    n, h, w, _ = images.shape
    oy = jax.random.randint(key, (n,), 0, h - out + 1)
    # graftlint: disable=GL101 -- fold_in(key, 1) is a stream disjoint from the raw key; raw+folded pairing is deliberate to keep recorded augmentation trajectories stable
    ox = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, w - out + 1)
    return _take_crops(images, oy, ox, out, out)


def hflip_batch(key: jax.Array, images: jax.Array) -> jax.Array:
    """Per-image random horizontal flip, p=0.5
    (``cifar10/data_loader.py:86``)."""
    flip = jax.random.bernoulli(key, shape=(images.shape[0],))
    return jnp.where(flip[:, None, None, None], images[:, :, ::-1, :], images)


def cutout_batch(key: jax.Array, images: jax.Array, length: int) -> jax.Array:
    """Square cutout mask (``Cutout``, ``cifar10/data_loader.py:57-76`` —
    defined in the reference but not wired into its transform; exposed here
    behind a flag). Centers are uniform over the image; squares clip at the
    borders, exactly like the reference's ``np.clip`` logic."""
    n, h, w, _ = images.shape
    cy = jax.random.randint(key, (n,), 0, h)
    # graftlint: disable=GL101 -- fold_in(key, 1) is a stream disjoint from the raw key; raw+folded pairing is deliberate to keep recorded augmentation trajectories stable
    cx = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, w)
    ys = jnp.arange(h)[None, :, None]
    xs = jnp.arange(w)[None, None, :]
    half = length // 2
    cy, cx = cy[:, None, None], cx[:, None, None]
    mask = (ys >= cy - half) & (ys < cy + half) & (xs >= cx - half) & (xs < cx + half)
    return jnp.where(mask[..., None], 0.0, images)


def augment_batch(
    key: jax.Array,
    images: jax.Array,
    pad: int = 4,
    use_cutout: bool = False,
    cutout_length: int = 16,
) -> jax.Array:
    """Jit'd train-time augmentation: random crop (pad 4) + horizontal flip
    [+ optional cutout] — the live non-IID pipeline of
    ``_data_transforms_cifar10`` (``cifar10/data_loader.py:83-96``), run
    on-device as whole-batch ops (3 RNG draws + 2 batched gathers for the
    full pool, no per-image key splitting)."""
    k_crop, k_flip, k_cut = jax.random.split(key, 3)
    out = random_crop_batch(k_crop, images, pad)
    out = hflip_batch(k_flip, out)
    if use_cutout:
        out = cutout_batch(k_cut, out, cutout_length)
    return out


def crop_flip_draws(
    key: jax.Array, n: int, pad: int = 4
) -> Tuple[jax.Array, jax.Array]:
    """The crop offsets ``off`` (``[n, 2]`` int32 in ``[0, 2*pad]``, row
    then column) and flips (``[n]`` bool) that :func:`augment_batch` draws
    from ``key`` — the same split, the same ``randint`` and ``bernoulli``,
    so either path replays the other's trajectory from one key (the third
    subkey is cutout's and stays unused here)."""
    k_crop, k_flip, _k_cut = jax.random.split(key, 3)
    off = jax.random.randint(k_crop, (n, 2), 0, 2 * pad + 1)
    flip = jax.random.bernoulli(k_flip, shape=(n,))
    return off, flip


def select_crop_flip(
    raw: jax.Array,
    off: jax.Array,
    flip: jax.Array,
    mean,
    std,
    image_shape: Optional[Tuple[int, int, int]] = None,
    pad: int = 4,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Zero-pad(``pad``) + crop at ``off`` + flip + normalize of uint8
    image rows as ONE dense pass: ``out[i, y, x] = raw[i, y + oy - pad,
    fx(x) + ox - pad]`` (``fx(x) = W - 1 - x`` for a flipped image), exact
    0.0 outside the image, bit-identical (at f32) to
    ``hflip(crop(pad(normalize_images(raw))))`` with the same draws.

    ``raw`` is ``[n, H, W, C]`` uint8 or the flat rows ``[n, H*W*C]`` the
    step keeps resident (then ``image_shape=(H, W, C)``); the result is
    ``[n, H, W, C]`` in ``out_dtype``, cast as the last op.

    The selection runs on the raw bytes, as two one-hot contractions on
    ``[n, H, W*C]`` (rows, then columns with the flip folded in). The
    pixels go in as ``value + 1``: 1..256 and the one-hots are exact in
    bf16, each output element has at most one non-zero term and the
    accumulator is f32, so what comes out is exactly ``value + 1`` inside
    the image and exactly 0 in the padding, which no pixel can be. The
    channels never stand alone in a minor dimension (which the TPU tiles
    to 128 lanes — the gather/pad/select chain this replaces moved 9.7 GB
    for a 31 MB pool, PERF.md section 6). Normalization comes last, in
    :func:`normalize_images`' own order, and the padding is masked to the
    exact zero the old chain padded with."""
    if raw.dtype != jnp.uint8:
        raise ValueError(
            f"select_crop_flip selects on raw uint8 pixels; got {raw.dtype}")
    if raw.ndim == 4:
        image_shape = tuple(int(d) for d in raw.shape[1:])
    elif raw.ndim != 2 or image_shape is None:
        raise ValueError(
            "select_crop_flip takes [n, H, W, C] images, or flat "
            f"[n, H*W*C] rows with image_shape; got {raw.shape}")
    h, w, c = image_shape
    n = raw.shape[0]
    pix = raw.reshape(n, h, w * c).astype(jnp.bfloat16) + 1
    ys, xs = jnp.arange(h), jnp.arange(w)
    src_y = ys[None, :] + (off[:, 0] - pad)[:, None]               # [n, H]
    fx = jnp.where(flip[:, None], w - 1 - xs[None, :], xs[None, :])
    src_x = fx + (off[:, 1] - pad)[:, None]                        # [n, W]
    # Column q = (x, ch) of the output reads column (src_x[x], ch) of the
    # source: one [W*C, W*C] one-hot per image. A source row or column
    # outside the image matches nothing and selects the 0.
    src_q = (src_x[:, :, None] * c + jnp.arange(c)).reshape(n, w * c)
    sel_y = src_y[:, :, None] == ys                                # [n, y, k]
    sel_q = jnp.arange(w * c)[:, None] == src_q[:, None, :]        # [n, k, q]
    rows = jnp.einsum("nyk,nkq->nyq", sel_y.astype(jnp.bfloat16), pix,
                      preferred_element_type=jnp.float32)
    sel = jnp.einsum("nyk,nkq->nyq", rows.astype(jnp.bfloat16),
                     sel_q.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    sel = sel.reshape(n, h, w, c)
    x = (sel - 1.0) / 255.0
    x = (x - jnp.asarray(mean)) / jnp.asarray(std)
    return jnp.where(sel > 0, x, 0.0).astype(jnp.dtype(out_dtype))


def augment_normalize(
    key: jax.Array,
    raw: jax.Array,
    mean,
    std,
    pad: int = 4,
    out_dtype=jnp.float32,
    image_shape: Optional[Tuple[int, int, int]] = None,
) -> jax.Array:
    """The uint8 ingest: dequant + per-channel normalize + random
    crop(``pad``) + horizontal flip in one pass over the raw bytes
    (:func:`select_crop_flip`), bit-identical (at f32) to
    ``augment_batch(key, normalize_images(raw, mean, std))``.

    ``raw``: ``[N, H, W, C]`` uint8, or flat ``[N, H*W*C]`` rows with
    ``image_shape``; ``mean``/``std``: per-channel constants.
    ``out_dtype`` is applied as the LAST op, so the bf16 scoring path
    (``scoring_dtype="bfloat16"``) emits bf16 activations directly — one
    rounding of the exact f32 value.

    The crop/flip draws replay ``augment_batch``'s key consumption exactly
    (:func:`crop_flip_draws`), so a trajectory is reproducible from the
    same JAX key on either path."""
    off, flip = crop_flip_draws(key, raw.shape[0], pad)
    return select_crop_flip(raw, off, flip, mean, std,
                            image_shape=image_shape, pad=pad,
                            out_dtype=out_dtype)


def next_pool(
    stream: ShardStream,
    key: jax.Array,
    pool_size: int,
) -> Tuple[ShardStream, jax.Array]:
    """Pull the next ``pool_size`` slot positions from a wrapping shuffled
    stream.

    Functional mirror of the reference's presampling iterator: a shuffled
    DataLoader consumed batch-by-batch, recreated (reshuffled) when
    exhausted (``Trainer.get_next``, ``pytorch_collab.py:74-82``). Returns
    the advanced stream state and ``pool_size`` slot indices into the shard.
    """
    length = stream.perm.shape[0]
    needs_reshuffle = stream.cursor + pool_size > length
    perm = jax.lax.cond(
        needs_reshuffle,
        lambda: jax.random.permutation(key, length).astype(stream.perm.dtype),
        lambda: stream.perm,
    )
    cursor = jnp.where(needs_reshuffle, 0, stream.cursor)
    slots = jax.lax.dynamic_slice(perm, (cursor,), (pool_size,))
    return ShardStream(perm=perm, cursor=cursor + pool_size), slots


@dataclasses.dataclass
class ShardedDataset:
    """Device-resident dataset with per-worker shards.

    The reference ships each fork a pickled per-worker presampling loader
    plus shared global loaders (``pytorch_collab.py:282-289``). Here, in
    single-controller SPMD, the full train/test arrays are device-resident
    (replicated) and each worker's shard is a row of a ``[W, L]`` index
    matrix — shards of unequal length (Dirichlet!) are cyclically tiled to
    the max length ``L`` so shapes are static for XLA.
    """

    x_train: jax.Array        # [N, H, W, C] uint8 (un-normalized; normalize in-step)
    y_train: jax.Array        # [N] int32
    x_test: jax.Array         # [Nt, H, W, C] uint8
    y_test: jax.Array         # [Nt] int32
    shard_indices: jax.Array  # [W, L] int32 — global ids, cyclically padded
    shard_sizes: jax.Array    # [W] int32 — true (unpadded) shard lengths
    mean: np.ndarray
    std: np.ndarray
    num_classes: int
    synthetic: bool = True    # False when loaded from real on-disk bytes

    @property
    def n_train(self) -> int:
        return int(self.x_train.shape[0])

    @property
    def n_test(self) -> int:
        return int(self.x_test.shape[0])

    @property
    def n_workers(self) -> int:
        return int(self.shard_indices.shape[0])

    def gather_batch(self, indices: jax.Array, train: bool = True) -> Batch:
        """Gather a normalized batch by global index (the in-graph analogue
        of dataset ``__getitem__`` + collate)."""
        x = self.x_train if train else self.x_test
        y = self.y_train if train else self.y_test
        images = normalize_images(x[indices], self.mean, self.std)
        return Batch(index=indices.astype(jnp.int32), image=images, label=y[indices])


def make_sharded_dataset(
    train: Tuple[np.ndarray, np.ndarray],
    test: Tuple[np.ndarray, np.ndarray],
    shards: List[np.ndarray],
    mean: np.ndarray,
    std: np.ndarray,
    num_classes: int,
    synthetic: bool = True,
    device_resident: bool = True,
) -> ShardedDataset:
    """Build a :class:`ShardedDataset` from host arrays + partition output.

    Cyclic tiling of short shards keeps shapes static without biasing much:
    each sample of a short shard simply appears ⌈L/len⌉ times in its row —
    the same effect as the reference's wrapping presampling iterator
    re-traversing a short shard more often per global step.
    """
    x_train, y_train = train
    x_test, y_test = test
    max_len = max(len(s) for s in shards)
    rows = []
    for s in shards:
        reps = int(np.ceil(max_len / len(s)))
        rows.append(np.tile(s, reps)[:max_len])
    shard_indices = np.stack(rows).astype(np.int32)
    shard_sizes = np.array([len(s) for s in shards], np.int32)
    # device_resident=False (data_placement="sharded"): the full train
    # arrays stay host-side — the step consumes materialized per-worker
    # shard arrays instead, and eval gathers from the host copy.
    conv_x = jnp.asarray if device_resident else np.asarray
    conv_y = ((lambda a: jnp.asarray(a, jnp.int32)) if device_resident
              else (lambda a: np.asarray(a, np.int32)))
    return ShardedDataset(
        x_train=conv_x(x_train),
        y_train=conv_y(y_train),
        x_test=jnp.asarray(x_test),
        y_test=jnp.asarray(y_test, jnp.int32),
        shard_indices=jnp.asarray(shard_indices),
        shard_sizes=jnp.asarray(shard_sizes),
        mean=mean,
        std=std,
        num_classes=num_classes,
        synthetic=synthetic,
    )


def init_shard_streams(key: jax.Array, n_workers: int, shard_len: int) -> ShardStream:
    """Initial per-worker stream state, stacked on a leading worker axis
    (sharded over the mesh in the SPMD step)."""
    keys = jax.random.split(key, n_workers)
    perms = jax.vmap(lambda k: jax.random.permutation(k, shard_len).astype(jnp.int32))(keys)
    return ShardStream(perm=perms, cursor=jnp.zeros((n_workers,), jnp.int32))


def eval_batches(
    n: int, batch_size: int
) -> List[Tuple[np.ndarray, int]]:
    """Host-side fixed-size eval batching plan: list of (index array, valid
    count); the last batch wraps (padding samples are masked out by the
    caller using the valid count). Mirrors ``Trainer.evaluate``'s full-pass
    semantics (``pytorch_collab.py:201-234``) with static shapes."""
    out = []
    for start in range(0, n, batch_size):
        end = min(start + batch_size, n)
        idx = np.arange(start, start + batch_size) % n
        out.append((idx.astype(np.int32), end - start))
    return out
