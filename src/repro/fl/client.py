"""Client local training: SGD epochs, FedProx proximal term, probing epoch.

All entry points are jit-compiled once per (task, padded-size) bucket; client
datasets are padded to power-of-two buckets with a validity mask so the jit
cache stays small across heterogeneous client sizes.

``make_parallel_local_train`` is the pod-scale path: K clients' local
training as one vmapped/pjit-able step (clients on the mesh ``data`` axis)
over the device-resident training rows, each client's batches gathered by
row index — the TPU-native analogue of the paper's multi-process simulator.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Any


def _bucket_cap(n: int) -> int:
    """Padded shard size: next power of two, minimum 8."""
    return max(8, 1 << (n - 1).bit_length())


def _bucket_geometry(n: int, batch_size: int) -> Tuple[int, int, int]:
    """(cap, batch_size, n_batches) for an n-sample client shard — the single
    source of the padding/batching rule shared by ``local_train`` and the
    vmapped executor (``repro.fl.engine``); diverging copies would silently
    break their numerical parity."""
    cap = _bucket_cap(n)
    bs = min(batch_size, cap)
    return cap, bs, cap // bs


def _pad_bucket(x: np.ndarray, y: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    n = len(y)
    cap = _bucket_cap(n)
    pad = cap - n
    xpad = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)])
    ypad = np.concatenate([y, np.zeros((pad,) + y.shape[1:], y.dtype)])
    mask = np.concatenate([np.ones(n, np.float32), np.zeros(pad, np.float32)])
    return xpad, ypad, mask


@functools.lru_cache(maxsize=64)
def _make_epoch_fn(task, batch_size: int, n_batches: int, mu: float):
    """One local epoch = n_batches SGD steps over a (n_batches*batch,) shard."""

    def prox_loss(p, batch, p_global):
        l = task.loss(p, batch)
        if mu > 0.0:
            sq = sum(jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32)))
                     for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p_global)))
            l = l + 0.5 * mu * sq
        return l

    @jax.jit
    def epoch(params, p_global, x, y, mask, lr):
        def step(params, sl):
            xb, yb, mb = sl
            loss, g = jax.value_and_grad(prox_loss)(params, {"x": xb, "y": yb, "mask": mb},
                                                    p_global)
            params = jax.tree.map(
                lambda p, gr: (p.astype(jnp.float32) - lr * gr.astype(jnp.float32)
                               ).astype(p.dtype), params, g)
            return params, loss

        xs = (x.reshape((n_batches, batch_size) + x.shape[1:]),
              y.reshape((n_batches, batch_size) + y.shape[1:]),
              mask.reshape((n_batches, batch_size)))
        params, losses = jax.lax.scan(step, params, xs)
        return params, losses.mean()

    return epoch


def local_train(
    task,
    params: Params,
    x: np.ndarray,
    y: np.ndarray,
    *,
    epochs: int,
    lr: float,
    batch_size: int = 32,
    prox_mu: float = 0.0,
    seed: int = 0,
) -> Tuple[Params, np.ndarray]:
    """Run ``epochs`` local epochs. Returns (params, per-epoch mean losses).
    losses[0] is the probing loss the FedRank scheme reports to the server."""
    rng = np.random.default_rng(seed)
    xpad, ypad, mask = _pad_bucket(x, y)
    cap, bs, nb = _bucket_geometry(len(y), batch_size)
    epoch_fn = _make_epoch_fn(task, bs, nb, float(prox_mu))
    p_global = params
    losses = []
    for e in range(epochs):
        perm = rng.permutation(cap)
        params, l = epoch_fn(params, p_global, xpad[perm][: nb * bs],
                             ypad[perm][: nb * bs], mask[perm][: nb * bs],
                             jnp.asarray(lr, jnp.float32))
        losses.append(float(l))
    return params, np.asarray(losses)


def probing_epoch(task, params: Params, x: np.ndarray, y: np.ndarray, *,
                  lr: float, batch_size: int = 32, prox_mu: float = 0.0,
                  seed: int = 0) -> Tuple[Params, float]:
    """The paper's "early exit" probe: exactly one local epoch; returns the
    partially-trained params (reused if the device is selected) + probe loss."""
    params, losses = local_train(task, params, x, y, epochs=1, lr=lr,
                                 batch_size=batch_size, prox_mu=prox_mu, seed=seed)
    return params, float(losses[0])


# ---------------------------------------------------------------------------
# Pod-scale parallel client training (vmapped; shard clients over "data")
# ---------------------------------------------------------------------------


def make_parallel_local_train(task, *, batch_size: int, n_batches: int,
                              epochs: int, prox_mu: float = 0.0,
                              stacked_params: bool = False) -> Callable:
    """Returns f(init_params, x, y, rows (K, epochs, n_batches*batch_size),
    lr) -> (stacked client params (K, ...), per-epoch mean losses (K, epochs)).

    ``x`` / ``y`` are the whole training set, resident on the device, with
    an all-zero padding row last (:meth:`FederatedData.resident_train`);
    they are shared operands, not stacked per client.  ``rows`` holds each
    client's per-epoch batches as absolute row indices, already in the
    shuffled order of :func:`local_train`; padded slots point at the
    padding row, which also masks them out of the loss.  Each epoch
    gathers its rows inside the step, so the batches hold exactly the
    values :func:`local_train`'s padded, shuffled shard holds.

    vmap over the client axis; under pjit the K axis is sharded over the mesh
    ``data`` axis, so each chip simulates a slice of the cohort.

    * ``stacked_params=True`` vmaps over a per-client leading axis of
      ``init_params`` too (each client resumes from its own params, e.g. the
      probe-stage output); otherwise the single pytree is broadcast.  The
      FedProx proximal term anchors to each client's own init params — the
      same semantics as the sequential :func:`local_train`.
    * ``losses[:, 0]`` is the probe loss the FedRank scheme reports.
    """

    def one_client(p_init, x, y, rows, lr):
        pad_row = x.shape[0] - 1

        def prox_loss(p, batch):
            l = task.loss(p, batch)
            if prox_mu > 0.0:
                sq = sum(jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32)))
                         for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p_init)))
                l = l + 0.5 * prox_mu * sq
            return l

        def sgd_step(params, sl):
            xb, yb, mb = sl
            loss, g = jax.value_and_grad(prox_loss)(params, {"x": xb, "y": yb, "mask": mb})
            params = jax.tree.map(
                lambda p, gr: (p.astype(jnp.float32) - lr * gr.astype(jnp.float32)
                               ).astype(p.dtype), params, g)
            return params, loss

        def epoch(params, re):
            xe, ye = x[re], y[re]
            me = (re != pad_row).astype(jnp.float32)
            xs = (xe.reshape((n_batches, batch_size) + x.shape[1:]),
                  ye.reshape((n_batches, batch_size) + y.shape[1:]),
                  me.reshape((n_batches, batch_size)))
            params, losses = jax.lax.scan(sgd_step, params, xs)
            return params, losses.mean()

        params, ep_losses = jax.lax.scan(epoch, p_init, rows)
        return params, ep_losses

    def parallel(p_init, x, y, rows, lr):
        return jax.vmap(one_client,
                        in_axes=(0 if stacked_params else None, None, None, 0,
                                 None))(p_init, x, y, rows, lr)

    return parallel
