"""Plain reference arithmetic in NumPy, shared by the task, merge and policy
modules of the benchmark.

Imports nothing of the program.  Every function takes a precision:

* ``F64`` -- the reference: float64 throughout;
* ``BF16`` -- the control for float32 at default matmul precision: every
  operand and result rounded to bfloat16, products accumulated in float32
  (as the MXU does).

Also here, because every task is fed by it: the client-data feed of the
server's executors (shards padded with masked rows to a power of two, at
least 8; per epoch a shuffle of the padded rows drawn from the request's
seed, cut to whole batches), and the availability trace.

What :mod:`bench.check` asks of a task module's reference
(``bench/tasks/<model.task>.py``):

* ``train(p0, x, y, mask, order, lr, prec=F64)`` -- local training of a
  chunk of C clients.  ``p0`` has the structure of the program's params
  (nested dicts, lists and tuples), each leaf stacked over the C clients,
  in the dtype and the kind of array it was recorded in (NumPy or
  ``jax.Array``); at one client a chunk it is a view of what was
  recorded, so it is read, never written.  It returns the trained params
  in the same structure, as NumPy or device arrays.
* ``loss(p, x, y)`` -- the (C,) loss of each client's params on its rows,
  as NumPy or device arrays.
* The module states in its docstring which reference it computes: float64
  NumPy, as here, or -- where that could not finish within a run, as for a
  transformer at its published widths -- straightforward ``jax.numpy`` in
  float32 under ``jax.default_matmul_precision("highest")``, computed in
  blocks so that it fits.  Either way it imports nothing of the program,
  and its control is the step one precision below the configuration's
  (:data:`bench.check.CONTROL`).
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

F64 = "float64"
BF16 = "bfloat16"
_BF16 = np.dtype(ml_dtypes.bfloat16)


def rnd(a, prec):
    """Storage rounding: float64, or bfloat16 for ``BF16``."""
    a = np.asarray(a)
    if prec == F64:
        return a.astype(np.float64)
    return a.astype(np.float32).astype(_BF16)


def f(a, prec):
    """Elementwise work: float64 for the reference, float32 otherwise."""
    return np.asarray(a, np.float64 if prec == F64 else np.float32)


def mm(a, b, prec):
    """Batched ``a @ b`` at the precision's matmul."""
    if prec == F64:
        return np.matmul(np.asarray(a, np.float64), np.asarray(b, np.float64))
    return rnd(np.matmul(rnd(a, prec).astype(np.float32),
                         rnd(b, prec).astype(np.float32)), prec)


def params_as(p: dict, prec) -> dict:
    return {k: rnd(v, prec) for k, v in p.items()}


# ---------------------------------------------------------------------------
def bucket(n: int, batch_size: int):
    """``(cap, batch, n_batches)``: a shard of ``n`` rows padded to the next
    power of two (at least 8), cut into whole batches."""
    cap = max(8, 1 << (n - 1).bit_length())
    bs = min(batch_size, cap)
    return cap, bs, cap // bs


def feed(x: np.ndarray, y: np.ndarray, seed: int, epochs: int,
         batch_size: int):
    """One client's padded shard and batch order: ``(x (cap, ...), y (cap,),
    mask (cap,), order (epochs, n_batches, batch))``."""
    n = len(y)
    cap, bs, nb = bucket(n, batch_size)
    xp = np.zeros((cap,) + x.shape[1:], x.dtype)
    yp = np.zeros((cap,) + y.shape[1:], y.dtype)
    xp[:n], yp[:n] = x, y
    mask = np.zeros(cap, np.float64)
    mask[:n] = 1.0
    rng = np.random.default_rng(seed)
    order = np.array([rng.permutation(cap)[:nb * bs] for _ in range(epochs)],
                     np.int64).reshape(epochs, nb, bs)
    return xp, yp, mask, order


# ---------------------------------------------------------------------------
def online_mask(t_start, state, offsets, period_s, src, phase_s, t_s,
                online_codes) -> np.ndarray:
    """(n,) online flag of every fleet device at virtual time ``t_s``: a
    device is online iff its source trace's segment holding ``(t + phase)
    mod period`` is in an online state."""
    tau = np.mod(t_s + np.asarray(phase_s, np.float64), period_s)
    out = np.zeros(len(src), bool)
    online = np.isin(np.arange(int(np.max(state)) + 1), online_codes)
    for d in np.unique(src):
        lo, hi = int(offsets[d]), int(offsets[d + 1])
        members = np.flatnonzero(src == d)
        seg = np.searchsorted(t_start[lo:hi], tau[members], side="right") - 1
        out[members] = online[state[lo:hi][seg]]
    return out
