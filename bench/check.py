"""Whether the timed path produced the right answers: the numbers compared.

For each compared aggregation (the first one, from the benchmark's own
initial weights, and a seeded sample of the window's) the plain reference
recomputes every stage from that stage's inputs -- the task's training in
``bench/tasks/``, the aggregator's merge in ``bench/merges/``, the policy's
choice in ``bench/policies/`` -- and the benchmark reads one number per
kind of answer:

* ``fit_gap`` -- every client of the first aggregation, trained from the
  benchmark's initial weights: the gap between the loss its trained params
  reach on its own rows and the loss the reference's reach, over the
  reference's decrease of that loss, ``|L(p) - L(p_ref)| / |L(p_start) -
  L(p_ref)|``; the worst client.  Only there does one local epoch take the
  loss down far (some 1.8 nats): once the global model fits the clients'
  rows, a client's decrease falls to a few thousandths of a nat and the
  ratio over it swings with rounding, in the program and the reference
  alike.
* ``step_gap`` -- every client trained in a compared aggregation, the
  first and the window's: the gap between the norm of the change its
  params made and the norm of the reference's change, leaf by leaf, over
  the reference's norm of that leaf or of the median leaf, whichever is
  larger, the worst leaf; leaves whose reference change is under a
  thousandth of the median leaf's are left out.  The median client of each
  aggregation, the worst aggregation: the worst of 25-100 clients swings
  with the few whose late, small updates rounding moves most.  The norms,
  not the norm of the difference: a client's 102 SGD steps turn rounding
  into params that point elsewhere by tenths of their update.
* ``merge_err`` -- the merged global model against the reference merge of
  the same uploaded client params, relative to the merge's own update:
  over the leaves, the worst ``|p - p_ref| / |p_ref - p_start|``, leaves
  whose reference update is under a thousandth of the median leaf's left
  out; ``inf`` where the merge saw another number of updates than the
  aggregation reports merged, or the server went on with another global
  model than the merge's.
* the policy's own numbers (``select_gap`` for FedRank);
* ``offline`` -- over every aggregation of the run, devices that the policy
  was offered as online, or chose, while the trace says they were offline:
  an exact count.

``numbers(..., control=prec)`` reads the same numbers for the control: the
reference computed at the precision ``prec`` below the configuration's,
put in the program's place.

Parameter trees may nest dicts, lists and tuples (a transformer's
``layers/attn/wq``); :func:`leaves` names each leaf by its path, and a flat
dict is its own flattening.  What the program and the task module return
may be NumPy or ``jax.Array`` leaves in any float dtype (the contract of a
task module is in :mod:`bench.reference`).  Host memory stays bounded
whatever the model's size: no tree is brought to the host whole in
float64.  The reference trains the clients of a group in chunks of at most
``CHUNK_BYTES`` of float64 params, handed the start params as they were
recorded (a view, at one client a chunk); each norm and each leaf of
the merge is taken on blocks of at most ``ROW`` elements a client,
brought to the host in float64 only there; a client's reference state is
kept only for a later call of the same aggregation that resumes it.  So a
compared aggregation of a model of B float32 bytes adds one chunk of the
reference's output to the host's peak (B in float32, 2B in float64, at
one client a chunk) and a few blocks, whatever the number of clients.
"""
from __future__ import annotations

import numpy as np

from bench import fleet
from bench import reference as R

# The control's precision, by the configuration's matmul precision (float32
# params): bfloat16 is the step below float32 at ``default``.
CONTROL = {"default": R.BF16}

# float64 bytes of params that one call of the reference trains: the MLP's
# groups of up to 50 clients (80 MB) take one call; a model of 12.6M
# parameters or more trains one client a call
CHUNK_BYTES = 96 << 20
# elements of one client's leaf in one block of a norm or a merge (8 MiB in
# float64): every leaf of the MLP is one block
ROW = 1 << 20


def _path(prefix: str, key) -> str:
    return f"{prefix}/{key}" if prefix else str(key)


def leaves(tree, prefix: str = "") -> dict:
    """``{path: leaf}`` of a tree of dicts, lists and tuples, in the tree's
    own order: keys and indices joined by ``/``."""
    if isinstance(tree, dict):
        items = tree.items()
    elif isinstance(tree, (list, tuple)):
        items = enumerate(tree)
    else:
        return {prefix: tree}
    out = {}
    for k, v in items:
        out.update(leaves(v, _path(prefix, k)))
    return out


def rebuild(tree, leaf, prefix: str = ""):
    """``tree``'s structure with the leaf at each path replaced by
    ``leaf(path)``."""
    if isinstance(tree, dict):
        return {k: rebuild(v, leaf, _path(prefix, k)) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(rebuild(v, leaf, _path(prefix, i))
                          for i, v in enumerate(tree))
    return leaf(prefix)


def _stack(arrays: list):
    """The leaves stacked on a new client axis, in their dtype: one leaf
    stays where it lies (a view for NumPy), several are stacked on the
    host.  Not a broadcast view: a copy made of one lays the clients
    innermost, which changes the order of the reference's sums."""
    if len(arrays) == 1:
        return arrays[0][None]
    return np.stack(arrays)


def _blocks(n: int) -> list:
    """Slices of a leaf of ``n`` elements a client, at most ``ROW`` each;
    ``slice(None)`` where the leaf is one block."""
    if n <= ROW:
        return [slice(None)]
    return [slice(lo, lo + ROW) for lo in range(0, n, ROW)]


def _cut(a, s: slice, c: int = 1) -> np.ndarray:
    """Block ``s`` of each client's elements of leaf ``a`` (one client's, or
    ``c`` stacked), ``(c, block)`` on the host in the leaf's dtype."""
    if s != slice(None):
        a = a.reshape(c, -1)[:, s]       # cut where the leaf lies
    return np.asarray(a).reshape(c, -1)


def _f64(a, s: slice, c: int = 1) -> np.ndarray:
    return np.asarray(_cut(a, s, c), np.float64)


def _worst_leaf(upd: dict, dev: dict) -> float:
    """Over the leaves, the worst ``dev / upd`` (the norms of ``p - p_ref``
    and ``p_ref - p_start``), leaves whose update is under a thousandth of
    the median leaf's left out."""
    med = float(np.median(list(upd.values())))
    worst = 0.0
    for k, u in upd.items():
        if u < 1e-3 * med:
            continue
        d = dev[k]
        # a reference that did not move at all: any departure is infinite
        worst = max(worst, d / u if u > 0 else (0.0 if d == 0 else np.inf))
    return worst


def offline(avail: list, trace_view) -> int:
    """Devices offered as online, or chosen, while offline: ``avail`` holds
    ``(round, offered mask packed to bits, chosen ids)`` per policy call."""
    n = 0
    for round_idx, packed, chosen in avail:
        online = trace_view.online(round_idx)
        offered = np.unpackbits(packed, count=len(online)).astype(bool)
        n += int((offered & ~online).sum()
                 + (~online[np.asarray(chosen, np.int64)]).sum())
    return n


def _train(rec, index, init_params, task, config, control, out):
    lr, bs = config["client"]["lr"], config["client"]["local_batch"]
    resumed_later = {cid for _, reqs, _ in rec["train"]
                     for cid, _, _, _, resumed, _ in reqs if resumed}
    ref_state, ctl_state = {}, {}        # path -> leaf, by client
    steps = []                           # each client's worst-leaf gap
    for g_in, reqs, outs in rec["train"]:
        g = init_params if index == 0 else g_in
        g_leaves = leaves(g)
        per_chunk = max(1, CHUNK_BYTES // (8 * sum(
            int(np.size(v)) for v in g_leaves.values())))
        groups = {}
        for cid, x, y, epochs, resumed, seed in reqs:
            groups.setdefault((epochs, resumed, len(y)), []).append(
                (cid, x, y, seed))
        for (epochs, resumed, n), group in groups.items():
            for lo in range(0, len(group), per_chunk):
                chunk = group[lo:lo + per_chunk]
                cids = [c for c, *_ in chunk]
                xs, ys, masks, orders = (np.stack(a) for a in zip(*[
                    R.feed(x, y, seed, epochs, bs)
                    for _, x, y, seed in chunk]))

                def start(state):
                    if resumed:
                        return rebuild(g, lambda k: _stack(
                            [state[c][k] for c in cids]))
                    return rebuild(g, lambda k: _stack(
                        [g_leaves[k]] * len(cids)))

                p0 = start(ref_state)
                ref = task.train(p0, xs, ys, masks, orders, lr)
                ref_leaves = leaves(ref)
                _keep(ref_state, cids, ref_leaves, resumed_later)
                if control is None:
                    got = [leaves(outs[c]) for c in cids]
                else:
                    ctl = leaves(task.train(start(ctl_state), xs, ys, masks,
                                            orders, lr, control))
                    _keep(ctl_state, cids, ctl, resumed_later)
                    got = [{k: v[j] for k, v in ctl.items()}
                           for j in range(len(cids))]
                steps.extend(_step_gap(leaves(p0), ref_leaves, got))
                if index == 0:
                    _fit_gap(task, p0, ref, rebuild(g, lambda k: _stack(
                        [t[k] for t in got])), (xs[:, :n], ys[:, :n]), out)
                # freed before the next chunk trains
                del p0, ref, ref_leaves, got
    if steps:
        out["step_gap"] = max(out["step_gap"], float(np.median(steps)))


def _keep(state: dict, cids: list, stacked: dict, wanted: set) -> None:
    """Keep the clients' rows of ``stacked`` that a later call resumes from,
    each its own copy."""
    for j, c in enumerate(cids):
        if c in wanted:
            state[c] = {k: np.array(v[j]) if isinstance(v, np.ndarray)
                        else v[j] for k, v in stacked.items()}


def _fit_gap(task, p0, ref, got, rows, out) -> None:
    """``|L(p) - L(p_ref)| / |L(p_start) - L(p_ref)|`` of a chunk's clients
    on their real, unpadded rows."""
    l0, l_ref, l_got = (np.asarray(task.loss(p, *rows), np.float64)
                        for p in (p0, ref, got))
    # no training to do (zero epochs): the params come back as handed
    moved = np.abs(l0 - l_ref)
    gap = np.where(moved > 0, np.abs(l_got - l_ref)
                   / np.where(moved > 0, moved, 1.0),
                   np.where(l_got == l_ref, 0.0, np.inf))
    out["fit_gap"] = max(out["fit_gap"], float(np.max(gap)))


def _step_gap(p0: dict, ref: dict, got: list) -> np.ndarray:
    """(C,) each client's worst leaf: ``| |got - p0| - |ref - p0| |`` over
    the larger of ``|ref - p0|`` and the median leaf's.  ``p0`` and ``ref``
    map paths to leaves stacked over the C clients, ``got`` holds each
    client's ``{path: leaf}``; every difference is taken in float64."""
    c = len(got)
    r, d = {}, {}
    for k in ref:
        r2 = d2 = 0.0
        for s in _blocks(int(np.size(ref[k])) // c):
            start = _f64(p0[k], s, c)
            r2 = r2 + ((_f64(ref[k], s, c) - start) ** 2).sum(1)
            mine = np.concatenate([_f64(t[k], s) for t in got])
            d2 = d2 + ((mine - start) ** 2).sum(1)
        r[k], d[k] = np.sqrt(r2), np.sqrt(d2)
    med = np.median(np.stack(list(r.values())), axis=0)
    worst = np.zeros_like(med)
    for k in ref:
        den = np.maximum(r[k], med)
        gap = np.where(den > 0, np.abs(d[k] - r[k]) / np.where(den > 0, den, 1),
                       np.where(d[k] == 0, 0.0, np.inf))
        worst = np.maximum(worst, np.where(r[k] >= 1e-3 * med, gap, 0.0))
    return worst


def _merge_err(merge_leaf, g, clients: list, weights, lags, traffic,
               merged, control) -> float:
    """The merge's worst leaf against the reference merge of the same
    uploads, one block of one leaf at a time."""
    ups = [leaves(c) for c in clients]
    got = leaves(merged) if control is None else None
    upd, dev = {}, {}
    for k, gk in leaves(g).items():
        u2 = d2 = 0.0
        for s in _blocks(int(np.size(gk))):
            g_blk = _cut(gk, s)[0]
            c_blk = [_cut(c[k], s)[0] for c in ups]
            ref = merge_leaf(g_blk, c_blk, weights, lags, traffic)
            mine = (_f64(got[k], s)[0] if control is None else
                    merge_leaf(g_blk, c_blk, weights, lags, traffic, control))
            x = ref - np.asarray(g_blk, np.float64)
            u2 += x.dot(x)
            x = np.asarray(mine, np.float64) - ref
            d2 += x.dot(x)
        upd[k], dev[k] = np.sqrt(u2), np.sqrt(d2)
    return _worst_leaf(upd, dev)


def numbers(kept: dict, avail: list, init_params, config: dict,
            traffic: dict, trace_view, control=None) -> dict:
    """The compared numbers over the kept records (see module doc)."""
    task = fleet.module("tasks", config["model"]["task"])
    merge_leaf = fleet.module("merges", traffic["aggregator"]).merge_leaf
    out = {"fit_gap": 0.0, "step_gap": 0.0, "merge_err": 0.0,
           "offline": offline(avail, trace_view)}
    out.update(fleet.module("policies", traffic["policy"]).numbers(
        kept, traffic, control))
    for index, rec in sorted(kept.items()):
        _train(rec, index, init_params, task, config, control, out)
        if rec["merge"] is None:
            if rec["n_merged"]:
                out["merge_err"] = float("inf")     # updates never merged
            continue
        g_in, clients, weights, lags, merged = rec["merge"]
        if control is None and (len(clients) != rec["n_merged"]
                                or rec["g_out"] is not merged):
            # the merge saw fewer updates than the aggregation reports, or
            # its result is not the global model that the server went on with
            out["merge_err"] = float("inf")
            continue
        g = ((init_params if index == 0 else rec["g_in"]) if g_in is None
             else g_in)
        out["merge_err"] = max(out["merge_err"], _merge_err(
            merge_leaf, g, clients, weights, lags, traffic, merged, control))
    return out


class TraceView:
    """The scenario's availability trace as plain arrays (the fleet
    simulator is part of the server; the benchmark reads its tables)."""

    def __init__(self, pool, states: list):
        avail = pool.availability
        fleet = avail.fleet
        tr = fleet.trace
        self.spr = float(avail.seconds_per_round)
        self.arrays = (np.asarray(tr.t_start), np.asarray(tr.state),
                       np.asarray(tr.offsets), float(tr.period_s),
                       np.asarray(fleet.src), np.asarray(fleet.phase_s))
        self.codes = [states.index(s) for s in avail.online_states]
        self._memo = {}

    def online(self, round_idx: int) -> np.ndarray:
        if round_idx not in self._memo:
            self._memo[round_idx] = R.online_mask(
                *self.arrays, round_idx * self.spr, self.codes)
        return self._memo[round_idx]


def is_finite(tree) -> bool:
    return all(np.isfinite(np.asarray(v)).all()
               for v in leaves(tree).values())
