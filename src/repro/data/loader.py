"""Federated data container + batch iterators."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Iterator, List, Tuple

import numpy as np

from repro.data.synthetic import SyntheticClassificationDataset


@dataclass
class FederatedData:
    """Global dataset + per-client index partition."""

    train: SyntheticClassificationDataset
    test: SyntheticClassificationDataset
    client_indices: List[np.ndarray]
    # placement (None or a Mesh) -> the device-resident training rows
    _resident: dict = field(default_factory=dict, init=False, repr=False,
                            compare=False)

    @property
    def n_clients(self) -> int:
        return len(self.client_indices)

    @property
    def pad_row(self) -> int:
        """Index of the all-zero row :meth:`resident_train` appends: every
        padded slot of a client's shard reads it."""
        return len(self.train.y)

    def resident_train(self, mesh=None):
        """``(x, y)``: the training rows on the device with one all-zero row
        appended at :attr:`pad_row`, uploaded once per placement (the
        default device, or replicated over ``mesh``) and kept here, since
        the rows never change.  A set larger than device memory fails
        here, at the upload."""
        hit = self._resident.get(mesh)
        if hit is None:
            import jax

            from repro.obs.profiling import count_resident_upload

            x, y = self.train.x, self.train.y
            x = np.concatenate([x, np.zeros((1,) + x.shape[1:], x.dtype)])
            y = np.concatenate([y, np.zeros((1,) + y.shape[1:], y.dtype)])
            if mesh is None:
                hit = (jax.device_put(x), jax.device_put(y))
            else:
                from jax.sharding import NamedSharding, PartitionSpec as P

                replicated = NamedSharding(mesh, P())
                hit = (jax.device_put(x, replicated),
                       jax.device_put(y, replicated))
            count_resident_upload(*hit)
            self._resident[mesh] = hit
        return hit

    def client_size(self, k: int) -> int:
        return len(self.client_indices[k])

    def client_batches(self, k: int, batch_size: int, epoch_seed: int
                       ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        """One epoch of shuffled batches for client k (drops ragged tail only
        if the client has more than one batch)."""
        idx = self.client_indices[k].copy()
        rng = np.random.default_rng(epoch_seed)
        rng.shuffle(idx)
        if len(idx) <= batch_size:
            yield self.train.x[idx], self.train.y[idx]
            return
        n_full = len(idx) // batch_size
        for i in range(n_full):
            b = idx[i * batch_size:(i + 1) * batch_size]
            yield self.train.x[b], self.train.y[b]

    def label_histogram(self, k: int) -> np.ndarray:
        y = self.train.y[self.client_indices[k]]
        return np.bincount(y, minlength=self.train.n_classes)


def batch_iterator(x: np.ndarray, y: np.ndarray, batch_size: int, seed: int = 0
                   ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    rng = np.random.default_rng(seed)
    idx = rng.permutation(len(y))
    for i in range(0, len(idx) - batch_size + 1, batch_size):
        b = idx[i:i + batch_size]
        yield x[b], y[b]
