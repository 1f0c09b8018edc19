"""A whole run with the timed path broken underneath comes out not correct.

Each fault is planted in the program's own modules, below the hooks the
benchmark records through, so the run sees the broken outputs as the
program's.  The exchange between chips exists only in a cell whose
executor shards the cohort over several chips.
"""
import numpy as np
import pytest

from conftest import CELLS, SPEC


def _unchanged(monkeypatch):
    """Client training returns the state it started from."""
    from repro.fl.engine import VmappedExecutor

    orig = VmappedExecutor.run

    def run(self, task, global_params, requests, **kw):
        out = orig(self, task, global_params, requests, **kw)
        for r in requests:
            out.params[r.client_id] = (r.init_params if r.init_params
                                       is not None else global_params)
        return out

    monkeypatch.setattr(VmappedExecutor, "run", run)
    return "fit_gap"


def _half_batch(monkeypatch):
    """The merge leaves out half of the clients, averaging the rest."""
    import repro.fl.async_engine as async_engine
    import repro.fl.server as server

    sync, buffered = server.robust_aggregate, async_engine.buffered_aggregate
    sync = getattr(sync, "__wrapped__", sync)
    buffered = getattr(buffered, "__wrapped__", buffered)

    def half(seq):
        return list(seq)[:max(1, len(seq) // 2)]

    monkeypatch.setattr(server, "robust_aggregate",
                        lambda ps, ws, *a, **kw: sync(half(ps), half(ws),
                                                      *a, **kw))
    monkeypatch.setattr(
        async_engine, "buffered_aggregate",
        lambda g, ps, ws, lags, *a, **kw: buffered(
            g, half(ps), half(ws), half(lags), *a, **kw))
    return "merge_err"


def _altered_update(monkeypatch):
    """One client's update, in every executor call, comes out reversed."""
    from repro.fl.engine import VmappedExecutor

    orig = VmappedExecutor.run

    def run(self, task, global_params, requests, **kw):
        out = orig(self, task, global_params, requests, **kw)
        r = requests[0]
        start = r.init_params if r.init_params is not None else global_params
        out.params[r.client_id] = {
            k: 2 * np.asarray(start[k]) - np.asarray(v)
            for k, v in out.params[r.client_id].items()}
        return out

    monkeypatch.setattr(VmappedExecutor, "run", run)
    return "fit_gap"


def _exchange_left_out(monkeypatch):
    """A sharded cohort's results come back from the first chip only: the
    clients placed on the others return the model they were handed."""
    from repro.fl.engine import VmappedExecutor

    orig = VmappedExecutor.run

    def run(self, task, global_params, requests, **kw):
        out = orig(self, task, global_params, requests, **kw)
        if self.mesh is not None:
            per_chip = -(-len(requests) // self._mesh_axis_size())
            for r in requests[per_chip:]:
                out.params[r.client_id] = (r.init_params if r.init_params
                                           is not None else global_params)
        return out

    monkeypatch.setattr(VmappedExecutor, "run", run)
    return "fit_gap"


def _altered_cohort(monkeypatch):
    """FedRank's final cut swaps its best pick for the probe cohort's
    worst."""
    from repro.core.fedrank import FedRankPolicy

    orig = FedRankPolicy.select

    def select(self, ctx, probe_ids, probe_states):
        chosen = np.array(orig(self, ctx, probe_ids, probe_states))
        left = np.setdiff1d(probe_ids, chosen)
        chosen[0] = left[-1]
        return chosen

    monkeypatch.setattr(FedRankPolicy, "select", select)
    return "select_gap"


def _offline_offered(monkeypatch):
    """The fleet simulator reports every device online."""
    from repro.fl.simulation import DevicePool

    monkeypatch.setattr(DevicePool, "available",
                        lambda self: np.ones(self.n, bool))
    return "offline"


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "altered_update": _altered_update,
          "exchange_left_out": _exchange_left_out,
          "altered_cohort": _altered_cohort,
          "offline_offered": _offline_offered}


# Only FedRank ranks, and only a cell on several chips exchanges between
# them; every cell starts at an hour of the trace in which some devices are
# offline.
CHIPS = {w["name"]: w["chips"] for w in SPEC["workloads"]}
CASES = [(cell, fault) for cell in CELLS for fault in FAULTS
         if (fault != "altered_cohort" or ".fedrank" in cell)
         and (fault != "exchange_left_out" or CHIPS[cell] > 1)]


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_is_not_correct(run_cell, monkeypatch, cell, fault):
    number = FAULTS[fault](monkeypatch)
    out = run_cell(cell, seed=3000000077)
    assert out["correct"] is False
    check = out["checks"][number]
    assert check["value"] > check["limit"]


@pytest.mark.parametrize("scale,reads", [(0.0, 1.0), (2.0, 1.0), (1.0, 0.0)])
def test_step_gap_of_a_scaled_update(scale, reads):
    """An update left out reads 1, one doubled reads 1, the reference's own
    reads 0, whatever the leaves' sizes."""
    from bench.check import _step_gap

    rng = np.random.default_rng(5)
    p0 = {k: rng.normal(size=(3,) + s) for k, s in
          (("w", (4, 5)), ("b", (5,)), ("v", (5, 2)))}
    ref = {k: v + 10.0 ** -i * rng.normal(size=v.shape)
           for i, (k, v) in enumerate(p0.items())}
    got = {k: p0[k] + scale * (ref[k] - p0[k]) for k in p0}
    per_client = [{k: v[j] for k, v in got.items()} for j in range(3)]
    np.testing.assert_allclose(_step_gap(p0, ref, per_client), [reads] * 3,
                               atol=1e-12)
