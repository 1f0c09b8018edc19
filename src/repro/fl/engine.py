"""Round-execution engine: RoundPlan + pluggable ClientExecutors.

This module is the seam between *what* a round does and *how* its client
work is executed:

* :class:`RoundPlan` — the explicit stage structure of one FL round
  (probe → select → complete), emitted per policy by
  :func:`build_round_plan`.  Probing policies (FedRank, FedMarl) get a
  1-epoch probe stage over ``policy.probe_set(ctx)`` whose survivors
  complete the remaining ``l_ep - 1`` epochs; non-probing baselines get an
  empty probe stage and a full ``l_ep``-epoch completion stage.  The server
  executes any plan uniformly — no per-policy branching.
* :class:`ClientExecutor` — the protocol for running a batch of per-client
  local-training requests.  :class:`SequentialExecutor` is the reference
  implementation (one :func:`repro.fl.client.local_train` call per client,
  the seed repo's semantics).  :class:`VmappedExecutor` groups clients into
  power-of-two size buckets and runs each bucket's cohort as ONE
  jitted/vmapped step via :func:`repro.fl.client.make_parallel_local_train`
  over the device-resident training rows, staging only each client's
  shuffled row indices — optionally sharding the client axis over a mesh
  ``data`` axis (``repro.launch.mesh``), which is the TPU pod-scale path.
  Both executors replay identical per-client shuffle orders, so they
  produce numerically matching global models.

Executors are looked up by name (``FLConfig.executor``) through a small
registry so new execution backends (remote, failure-injecting) plug in
without touching the server.  Asynchrony is NOT an executor: executors
decide *how a batch of client work computes*, while the asynchronous engine
(:mod:`repro.fl.async_engine`, ``FLConfig.mode="async"``) decides *when*
each client's work starts, pauses and aggregates on a virtual clock.  The
registry's ``"async"`` entry is a convenience alias
(:class:`AsyncDispatchExecutor`) that flips the server into async mode
while delegating the actual batch compute to an inner executor; both
engines build their work items through the shared dispatch interface
(:func:`build_requests`).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Protocol, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl.client import (
    _bucket_geometry,
    local_train,
    make_parallel_local_train,
)
from repro.obs.profiling import (
    count_d2h,
    count_h2d,
    count_implicit_h2d,
    span,
    timed_call,
)

Params = Any


# ---------------------------------------------------------------------------
# Round plans
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class RoundPlan:
    """Explicit stage structure of one FL round.

    probe stage      — every device in ``probe_ids`` runs ``probe_epochs``
                       local epochs from the global params and reports its
                       loss (empty ``probe_ids`` skips the stage);
    select           — the policy cuts the cohort down to K survivors
                       (probing policies see the revealed probe states);
    completion stage — survivors run ``completion_epochs`` further epochs
                       (resuming from their probed params when probed) and
                       upload for aggregation.
    """

    probe_ids: np.ndarray
    probe_epochs: int
    completion_epochs: int

    @property
    def has_probe(self) -> bool:
        return len(self.probe_ids) > 0 and self.probe_epochs > 0


def build_round_plan(policy, ctx, l_ep: int) -> RoundPlan:
    """Adapt a SelectionPolicy into a RoundPlan.

    Policies may emit a custom plan via ``policy.plan_round(ctx, l_ep)``;
    otherwise the declared ``needs_probing`` capability maps onto the
    paper's two round shapes.  This is the only place that capability is
    consulted — the server just executes the plan.
    """
    plan_fn = getattr(policy, "plan_round", None)
    if plan_fn is not None:
        return plan_fn(ctx, l_ep)
    if getattr(policy, "needs_probing", False):
        probe_ids = np.asarray(policy.probe_set(ctx), dtype=np.int64)
        return RoundPlan(probe_ids, probe_epochs=1, completion_epochs=l_ep - 1)
    return RoundPlan(np.empty(0, np.int64), probe_epochs=0,
                     completion_epochs=l_ep)


# ---------------------------------------------------------------------------
# Client executors
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ClientRequest:
    """One client's local-training work item for a stage: its rows of the
    data source by index (``x`` / ``y`` gather them on the host)."""

    client_id: int
    data: Any                              # FederatedData the rows index
    rows: np.ndarray                       # row indices into data.train
    epochs: int
    seed: int
    init_params: Optional[Params] = None   # None => start from global params

    @property
    def x(self) -> np.ndarray:
        return self.data.train.x[self.rows]

    @property
    def y(self) -> np.ndarray:
        return self.data.train.y[self.rows]


@dataclass
class ExecutionResult:
    """Per-client outputs of a stage, keyed by client id."""

    params: Dict[int, Params] = field(default_factory=dict)
    losses: Dict[int, np.ndarray] = field(default_factory=dict)

    def block_until_ready(self) -> "ExecutionResult":
        """Fence every client's params (``jax.block_until_ready`` calls
        this on a result, as ``timed_call`` does around an executor)."""
        jax.block_until_ready(self.params)
        return self


# Seed strides for per-client local-training RNG: stage seeds are
# ``cfg.seed + stride * round + client_id`` so probe and completion stages
# of the same round never collide.  The async engine uses the SAME strides
# keyed by its aggregation-cycle index, which is what makes its
# buffer_size=K reduction bit-compatible with the synchronous path.
PROBE_SEED_STRIDE = 1000
COMPLETE_SEED_STRIDE = 2000


def build_requests(ids: Sequence[int], data, epochs: int, *, seed: int,
                   round_idx: int, stride: int,
                   init_params: Optional[Dict[int, Params]] = None
                   ) -> List[ClientRequest]:
    """Shared dispatch interface: one :class:`ClientRequest` per client id.

    ``data`` (a :class:`~repro.data.loader.FederatedData`) supplies each
    client's shard as its row indices (``data.client_indices[i]``, not a
    copy); ``init_params`` (id -> params) overrides the global starting
    point for clients that resume from probed state.  Both the synchronous
    server and the asynchronous engine build their stages through this
    function, so the two paths cannot drift in seeds or request shape.
    """
    init = init_params or {}
    with span("train.gather"):
        return [ClientRequest(int(i), data, data.client_indices[int(i)],
                              epochs=epochs,
                              seed=seed + stride * round_idx + int(i),
                              init_params=init.get(int(i)))
                for i in ids]


class ClientExecutor(Protocol):
    name: str

    def run(self, task, global_params: Params,
            requests: Sequence[ClientRequest], *, lr: float,
            batch_size: int, prox_mu: float) -> ExecutionResult: ...


def executor_label(ex) -> str:
    """The executor actually doing the work, wrappers unwrapped: registry
    ``name`` with any ``inner`` delegate in brackets — e.g. the ``"async"``
    alias around a vmapped executor reports ``"async[vmapped]"``.  This is
    what :class:`~repro.fl.server.RoundResult.executor` records, so
    benchmark reductions stop re-deriving it from config strings."""
    name = getattr(ex, "name", type(ex).__name__)
    inner = getattr(ex, "inner", None)
    if inner is not None:
        return f"{name}[{executor_label(inner)}]"
    return name


class SequentialExecutor:
    """Reference semantics: one ``local_train`` call per client, in order."""

    name = "sequential"

    def run(self, task, global_params, requests, *, lr, batch_size, prox_mu
            ) -> ExecutionResult:
        out = ExecutionResult()
        for req in requests:
            init = req.init_params if req.init_params is not None else global_params
            p, losses = local_train(task, init, req.x, req.y,
                                    epochs=req.epochs, lr=lr,
                                    batch_size=batch_size, prox_mu=prox_mu,
                                    seed=req.seed)
            out.params[req.client_id] = p
            out.losses[req.client_id] = losses
        return out


@functools.lru_cache(maxsize=256)
def _bucket_step(task, batch_size: int, n_batches: int, epochs: int,
                 prox_mu: float, stacked_params: bool):
    """Jitted whole-bucket step, cached per (task, geometry, epochs)."""
    fn = make_parallel_local_train(task, batch_size=batch_size,
                                   n_batches=n_batches, epochs=epochs,
                                   prox_mu=prox_mu,
                                   stacked_params=stacked_params)
    return jax.jit(fn)


class VmappedExecutor:
    """Pod-scale path: the whole cohort's local training as one jitted step.

    Clients are grouped into (data source, padded-size, epochs) buckets;
    each bucket is a single vmapped call over the client axis.  The
    training rows stay on the device (uploaded once per data source and
    placement, :meth:`FederatedData.resident_train`); a stage uploads only
    each client's host-drawn shuffle orders composed with its row indices,
    which the step gathers by, so results match
    :class:`SequentialExecutor` numerically.  Pass a ``Mesh`` (see
    :mod:`repro.launch.mesh`) to shard the client axis over the mesh
    ``data`` axis; the resident rows are then replicated.
    """

    name = "vmapped"

    def __init__(self, mesh=None):
        self.mesh = mesh

    def run(self, task, global_params, requests, *, lr, batch_size, prox_mu
            ) -> ExecutionResult:
        out = ExecutionResult()
        buckets: Dict[tuple, List[ClientRequest]] = {}
        for req in requests:
            if req.epochs <= 0:
                init = (req.init_params if req.init_params is not None
                        else global_params)
                out.params[req.client_id] = init
                out.losses[req.client_id] = np.zeros(0)
                continue
            cap, _, _ = _bucket_geometry(len(req.rows), batch_size)
            buckets.setdefault((id(req.data), cap, req.epochs), []).append(req)
        for (_, cap, epochs), reqs in buckets.items():
            self._run_bucket(task, global_params, reqs, cap, epochs, out,
                             lr=lr, batch_size=batch_size, prox_mu=prox_mu)
        return out

    def _run_bucket(self, task, global_params, reqs, cap, epochs, out, *,
                    lr, batch_size, prox_mu):
        # sub-spans and transfer counters (repro.obs.profiling) are no-ops
        # unless a profiler is active
        _, bs, nb = _bucket_geometry(cap, batch_size)
        take = nb * bs
        data = reqs[0].data
        k = len(reqs)
        # pad the client axis up to a multiple of the mesh data-axis size
        # (duplicates of the last client; results discarded) so sharding
        # never silently degrades to replicated execution
        n_pad = (-k) % self._mesh_axis_size() if self.mesh is not None else 0
        with span("train.pack"):
            # each client's shuffle orders, drawn as local_train draws them,
            # composed with its row indices; a padded slot reads the
            # all-zero padding row, as local_train's zero padding
            rows = np.empty((k + n_pad, epochs, take), np.int32)
            shard = np.empty(cap, np.int32)
            for j, req in enumerate(reqs):
                n = len(req.rows)
                shard[:n] = req.rows
                shard[n:] = data.pad_row
                rng = np.random.default_rng(req.seed)
                for e in range(epochs):
                    rows[j, e] = shard[rng.permutation(cap)[:take]]
            rows[k:] = rows[k - 1]
            stacked_init = any(req.init_params is not None for req in reqs)
            if stacked_init:
                inits = [req.init_params if req.init_params is not None
                         else global_params for req in reqs]
                inits += inits[-1:] * n_pad
                count_d2h("train", inits)
                p0 = jax.tree.map(
                    lambda *ls: np.stack([np.asarray(l) for l in ls]), *inits)
        with span("train.upload"):
            # uploaded by the first stage over this data and placement
            x, y = data.resident_train(self.mesh)
            rows = jnp.asarray(rows)
            if stacked_init:
                p0 = jax.tree.map(jnp.asarray, p0)
                count_h2d("train", rows, p0)
            else:
                # shared start (probe stage / vanilla rounds): pass the
                # single pytree and let vmap broadcast it inside XLA — no
                # K-fold copy; host leaves cross as the step's operands
                p0 = global_params
                count_h2d("train", rows)
                count_implicit_h2d("train", p0, rows)
            step = _bucket_step(task, bs, nb, epochs, float(prox_mu),
                                stacked_init)
            rows = self._shard(rows)
            p0 = self._shard_params(p0, stacked_init)
            lr = jnp.asarray(lr, jnp.float32)
            count_h2d("train", lr)
        # timed_call is a passthrough unless a profiler is active
        # (repro.obs.profiling), in which case the jitted bucket step is
        # fenced and charged per (cohort-size, epochs) geometry
        stacked, ep_losses = timed_call(
            f"vmapped.bucket_step[k={len(reqs)},ep={epochs}]",
            step, p0, x, y, rows, lr)
        # one device->host transfer per leaf, then cheap numpy views per
        # client — slicing on device would cost K x leaves dispatches
        with span("train.pull"):
            count_d2h("train", stacked, ep_losses)
            stacked = jax.tree.map(np.asarray, stacked)
            ep_losses = np.asarray(ep_losses)
        with span("train.split"):
            for j, req in enumerate(reqs):
                out.params[req.client_id] = jax.tree.map(lambda a, j=j: a[j],
                                                         stacked)
                out.losses[req.client_id] = ep_losses[j]

    def _mesh_axis_size(self) -> int:
        """Size of the mesh ``data`` axis (buckets are padded to a multiple)."""
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape)
                    ).get("data", 1)

    def _shard(self, args):
        """Place the client axis on the mesh ``data`` axis."""
        if self.mesh is None:
            return args
        from jax.sharding import NamedSharding, PartitionSpec as P

        shard = NamedSharding(self.mesh, P("data"))
        return jax.tree.map(lambda a: jax.device_put(a, shard), args)

    def _shard_params(self, p0, stacked_init: bool):
        """Stacked params shard over clients; a shared pytree is replicated."""
        if self.mesh is None:
            return p0
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = NamedSharding(self.mesh, P("data") if stacked_init else P())
        return jax.tree.map(lambda a: jax.device_put(a, spec), p0)


class AsyncDispatchExecutor:
    """Registry alias selecting the asynchronous engine.

    ``FLConfig(executor="async")`` is shorthand for
    ``FLConfig(mode="async")``: the server spots this executor's name and
    drives rounds through :class:`repro.fl.async_engine.AsyncRoundEngine`
    instead of the synchronous barrier loop.  Batch compute inside each
    dispatch wave is delegated to ``inner`` (default:
    :class:`SequentialExecutor`; pass ``inner="vmapped"`` to run each wave
    as one jitted step).
    """

    name = "async"

    def __init__(self, inner=None, **kw):
        if inner is None or isinstance(inner, str):
            self.inner = make_executor(inner or "sequential", **kw)
        else:
            self.inner = inner

    def run(self, task, global_params, requests, *, lr, batch_size, prox_mu
            ) -> ExecutionResult:
        return self.inner.run(task, global_params, requests, lr=lr,
                              batch_size=batch_size, prox_mu=prox_mu)


# ---------------------------------------------------------------------------
# Executor registry
# ---------------------------------------------------------------------------

_EXECUTORS: Dict[str, Callable[..., ClientExecutor]] = {}


def register_executor(name: str, factory: Callable[..., ClientExecutor]) -> None:
    if name in _EXECUTORS:
        raise ValueError(f"executor {name!r} already registered")
    _EXECUTORS[name] = factory


def make_executor(name: str, **kw) -> ClientExecutor:
    try:
        factory = _EXECUTORS[name]
    except KeyError:
        raise KeyError(f"unknown executor {name!r}; "
                       f"registered: {sorted(_EXECUTORS)}") from None
    return factory(**kw)


def available_executors() -> List[str]:
    return sorted(_EXECUTORS)


register_executor("sequential", SequentialExecutor)
register_executor("vmapped", VmappedExecutor)
register_executor("async", AsyncDispatchExecutor)
