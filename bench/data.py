"""The benchmark's data: an MNIST-shaped synthetic task and its splits.

A vectorised copy of the program's synthetic task (10 classes of 28x28
images: a smooth class template under a random shift and contrast, plus
pixel noise) that draws the same numbers from the same generator calls,
so it matches the program's generator element for element while making
the 70,000 images in one pass instead of one Python step per image.

Two splits over the training set:

* `xclass_split`: the FedAdp paper's nodes (arXiv:2012.00661 §V): IID
  nodes draw uniformly, x-class nodes draw from x random classes.
* `shard_split`: McMahan et al.'s pathological non-IID split
  (arXiv:1602.05629 §3): sort by label, cut into shards of equal size,
  give each client the same number of random shards.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Dataset(NamedTuple):
    x: np.ndarray  # (n, side, side, 1) float32 in [0, 1]
    y: np.ndarray  # (n,) int32


def templates(rng: np.random.Generator, num_classes: int,
              side: int) -> np.ndarray:
    """Smooth class templates in [0, 1]: a 7x7 normal grid per class,
    bilinearly upsampled to side x side."""
    low = rng.normal(size=(num_classes, 7, 7))
    xs = np.linspace(0, 6, side)
    x0 = np.clip(xs.astype(int), 0, 5)
    fx = xs - x0
    a = (1 - fx)[:, None] * (1 - fx)[None]
    b = fx[:, None] * (1 - fx)[None]
    c = (1 - fx)[:, None] * fx[None]
    d = fx[:, None] * fx[None]
    t = (low[:, x0][:, :, x0] * a + low[:, x0 + 1][:, :, x0] * b
         + low[:, x0][:, :, x0 + 1] * c + low[:, x0 + 1][:, :, x0 + 1] * d)
    t = t.astype(np.float32)
    t -= t.min(axis=(1, 2), keepdims=True)
    t /= t.max(axis=(1, 2), keepdims=True) + 1e-8
    return t


def _images(t: np.ndarray, n: int, seed: int, shift: int,
            noise: float) -> Dataset:
    num_classes, side, _ = t.shape
    r = np.random.default_rng(seed)
    y = r.integers(0, num_classes, size=n).astype(np.int32)
    dx = r.integers(-shift, shift + 1, size=n)
    dy = r.integers(-shift, shift + 1, size=n)
    contrast = r.uniform(0.7, 1.3, size=n).astype(np.float32)
    # np.roll(t[y], (dx, dy), axis=(0, 1)) for every image at once
    ar = np.arange(side)
    rows = (ar[None, :] - dx[:, None]) % side
    cols = (ar[None, :] - dy[:, None]) % side
    x = t[y[:, None, None], rows[:, :, None], cols[:, None, :]]
    x *= contrast[:, None, None]
    x += r.normal(scale=noise, size=x.shape).astype(np.float32)
    np.clip(x, 0.0, 1.5, out=x)
    x /= 1.5
    return Dataset(x[..., None], y)


def image_task(seed: int, num_train: int = 60000, num_test: int = 10000,
               num_classes: int = 10, side: int = 28, shift: int = 3,
               noise: float = 0.35) -> tuple[Dataset, Dataset]:
    """(train, test) of the synthetic task made from `seed`."""
    t = templates(np.random.default_rng(seed), num_classes, side)
    return (_images(t, num_train, seed + 1, shift, noise),
            _images(t, num_test, seed + 2, shift, noise))


def xclass_split(train: Dataset, spec: list, samples: int, seed: int,
                 num_classes: int = 10) -> list[Dataset]:
    """One node per entry of `spec`: ["iid", null] draws `samples` rows
    uniformly without replacement; ["xclass", x] draws them from x random
    classes."""
    rng = np.random.default_rng(seed)
    nodes = []
    for kind, x in spec:
        if kind == "iid":
            idx = rng.choice(len(train.y), size=samples, replace=False)
        elif kind == "xclass":
            classes = rng.choice(num_classes, size=x, replace=False)
            pool = np.flatnonzero(np.isin(train.y, classes))
            idx = rng.choice(pool, size=samples, replace=len(pool) < samples)
        else:
            raise ValueError(f"unknown node kind {kind!r}")
        nodes.append(Dataset(train.x[idx], train.y[idx]))
    return nodes


def shard_split(train: Dataset, clients: int, shards_per_client: int,
                seed: int) -> list[Dataset]:
    """McMahan's split: rows sorted by label, cut into
    clients * shards_per_client equal shards, each client given
    `shards_per_client` of them at random."""
    shards = clients * shards_per_client
    size = len(train.y) // shards
    order = np.argsort(train.y, kind="stable")[: shards * size]
    perm = np.random.default_rng(seed).permutation(shards)
    nodes = []
    for c in range(clients):
        mine = perm[c * shards_per_client:(c + 1) * shards_per_client]
        idx = np.concatenate([order[s * size:(s + 1) * size] for s in mine])
        nodes.append(Dataset(train.x[idx], train.y[idx]))
    return nodes


def make_nodes(traffic: dict) -> tuple[list[Dataset], Dataset]:
    """The cell's client datasets and test set, from its traffic file."""
    d = traffic["data"]
    train, test = image_task(d["seed"], d["num_train"], d["num_test"])
    split = d["split"]
    if split["kind"] == "xclass":
        nodes = xclass_split(train, split["nodes"], split["samples"],
                             split["seed"])
    elif split["kind"] == "shards":
        nodes = shard_split(train, split["clients"],
                            split["shards_per_client"], split["seed"])
    else:
        raise ValueError(f"unknown split {split['kind']!r}")
    return nodes, test
