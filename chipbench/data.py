"""Seeded tabular data: the benchmark's own copy of the math of the
program's ``TabularTask`` (the paper's controlled datasets, §4.3).

N samples of F features drawn as one Gaussian cluster per class with
class-dependent means, rotated and half warped through tanh.  Batches are
step-indexed: step ``s`` of a run reads epoch ``s // (N // B)`` of a
permutation drawn from ``(seed, epoch)``, so every seed gives every run
the same sizes in another order, and the rows of one epoch all differ.
"""
from __future__ import annotations

import numpy as np


class Task:
    def __init__(self, n_samples: int, n_features: int, n_classes: int,
                 seed: int):
        rng = np.random.default_rng(seed)
        means = rng.normal(0, 2.0, (n_classes, n_features))
        rot = np.linalg.qr(rng.normal(0, 1, (n_features, n_features)))[0]
        y = rng.integers(0, n_classes, n_samples)
        x = means[y] + rng.normal(0, 1, (n_samples, n_features))
        x = (x @ rot).astype(np.float32)
        x[:, ::2] = np.tanh(x[:, ::2])
        self.x, self.y = x, y.astype(np.int32)
        self.seed = seed
        self._order = (None, None)

    def slab(self, start: int, n_steps: int, batch: int, out=None):
        """Steps ``[start, start + n_steps)`` as one ``(n_steps, batch, F)``
        and ``(n_steps, batch)`` pair, written into ``out`` when given."""
        n = len(self.y)
        per_epoch = max(n // batch, 1)
        if out is None:
            out = (np.empty((n_steps, batch, self.x.shape[1]), np.float32),
                   np.empty((n_steps, batch), np.int32))
        xs, ys = out
        for j in range(n_steps):
            epoch, k = divmod(start + j, per_epoch)
            if self._order[0] != epoch:
                self._order = (epoch, np.random.default_rng(
                    np.random.SeedSequence([self.seed, epoch])).permutation(n))
            order = self._order[1]
            lo = (k * batch) % n
            idx = order[lo: lo + batch]
            if len(idx) < batch:
                idx = np.concatenate([idx, order[:batch - len(idx)]])
            xs[j], ys[j] = self.x[idx], self.y[idx]
        return xs, ys
