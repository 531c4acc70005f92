"""Seeded synthetic twins of the paper's UCI datasets.

A copy of the program's Gaussian-mixture twin generator, kept here so the
benchmark's data cannot move with the program.  Sizes and difficulty come
from the configuration file's ``dataset`` block; the signature
(features, classes, train/test rows) is the published one.
"""
from __future__ import annotations

import dataclasses
import zlib

import numpy as np


@dataclasses.dataclass(frozen=True)
class Twin:
    x_train: np.ndarray   # float32 [n_train, F], standardized
    y_train: np.ndarray   # int32   [n_train]
    x_test: np.ndarray    # float32 [n_test, F]
    y_test: np.ndarray    # int32   [n_test]
    n_classes: int


def make_twin(spec: dict, seed: int) -> Twin:
    """The twin of ``spec`` (a configuration's ``dataset`` block) for
    ``seed``; the same seed gives the same rows."""
    F, C = int(spec["n_features"]), int(spec["n_classes"])
    rng = np.random.default_rng(
        [int(seed), zlib.crc32(spec["name"].encode())])
    n_signal = max(2, int(F * (1.0 - spec["noise_features"])))
    m = int(spec["clusters_per_class"])
    D = int(spec["intrinsic_dim"])
    # each class is a mixture of m clusters drawn from a shared pool in a
    # low-dimensional space and interleaved across classes, embedded into
    # the feature space: no linear split separates the classes, trees do
    pool = rng.normal(0.0, spec["separation"], size=(C * m, D))
    assignment = rng.permutation(C * m).reshape(C, m)
    embed = rng.normal(0.0, 1.0 / np.sqrt(D), size=(D, n_signal))
    w0 = spec["primary_weight"] if m > 1 else 1.0
    comp_probs = np.full((m,), (1.0 - w0) / max(m - 1, 1))
    comp_probs[0] = w0

    def sample(n: int):
        y = rng.integers(0, C, size=n)
        comp = rng.choice(m, size=n, p=comp_probs)
        z = pool[assignment[y, comp]] + rng.normal(0.0, 1.0, size=(n, D))
        x = z @ embed + rng.normal(0.0, 0.5, size=(n, n_signal))
        if spec["label_noise"] > 0:
            flip = rng.random(n) < spec["label_noise"]
            y = np.where(flip, rng.integers(0, C, size=n), y)
        if n_signal < F:
            x = np.concatenate(
                [x, rng.normal(0.0, 1.0, size=(n, F - n_signal))], axis=1)
        return x.astype(np.float32), y.astype(np.int32)

    x_train, y_train = sample(int(spec["n_train"]))
    x_test, y_test = sample(int(spec["n_test"]))
    mu, sd = x_train.mean(0), x_train.std(0) + 1e-6
    x_train = ((x_train - mu) / sd).astype(np.float32)
    x_test = ((x_test - mu) / sd).astype(np.float32)
    return Twin(x_train, y_train, x_test, y_test, C)
