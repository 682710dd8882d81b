"""The comparison that decides ``correct`` fails when the timed path is
broken: the control (views served stale, the guarantee the configurations
state broken) and one planted fault of each kind a cell can have."""
import itertools

import numpy as np
import pytest

from bench.tests import tiny


@pytest.mark.parametrize("cell", [tiny.READ_CELL, tiny.WRITE_CELL])
def test_sound_run_is_correct(cell):
    run, out = tiny.run(cell)
    assert out["correct"], out["checks"]
    assert run.read_lat_s.size > 0
    assert out["failed"] == 0


@pytest.mark.parametrize("traffic", [
    {"arrival": {"kind": "open", "rate": 150.0, "burst_every_s": 0.5,
                 "burst_s": 0.1, "burst_factor": 3.0},
     "sources": {"dist": "zipf", "a": 1.1, "drift_every": 16},
     "unbound_share": 0.2},
    {"write_every_windows": 3}])
def test_other_traffic_policies_run_correct(traffic):
    """Traffic that only a data file sets: an open loop with bursts, Zipf
    sources with a drifting hot set and unbound reads; a writer paced by
    read windows."""
    cell = tiny.READ_CELL if "arrival" in traffic else tiny.WRITE_CELL
    run, out = tiny.run(cell, traffic=traffic)
    assert out["correct"], out["checks"]
    assert run.read_lat_s.size > 0 and out["failed"] == 0
    if cell == tiny.WRITE_CELL:
        assert run.writes_acked > 0


def test_altered_unbound_answer_fails(monkeypatch):
    """An unbound read's rows are checked too: one row left out."""
    from repro.core.plan import RowResult
    orig = RowResult.to_reach_result

    def short(self):
        res = orig(self)
        if res.reach.shape[0] > 1:
            res.reach, res.src_ids = res.reach[1:], res.src_ids[1:]
        return res

    monkeypatch.setattr(RowResult, "to_reach_result", short)
    _, out = tiny.run(tiny.READ_CELL, traffic={"unbound_share": 1.0})
    assert not out["correct"]
    assert out["checks"]["read_rows_wrong"]["value"] > 0


@pytest.mark.parametrize("cell", [tiny.READ_CELL, tiny.WRITE_CELL])
def test_control_stale_views_fails(cell):
    _, out = tiny.run(cell, control="stale_views")
    assert not out["correct"]
    assert out["checks"]["view_pairs_wrong"]["value"] > 0


@pytest.mark.parametrize("cell", [tiny.READ_CELL, tiny.WRITE_CELL])
def test_altered_answer_fails(cell, monkeypatch):
    """One entry of every served row is changed where rows are produced."""
    from repro.core.plan import RowResult
    orig = RowResult.to_reach_result

    def altered(self):
        res = orig(self)
        res.reach[:, 0] += 1
        return res

    monkeypatch.setattr(RowResult, "to_reach_result", altered)
    _, out = tiny.run(cell)
    assert not out["correct"]
    assert out["checks"]["read_rows_wrong"]["value"] > 0


def test_half_the_batch_left_out_fails(monkeypatch):
    """A window executes its first half and hands those answers to the
    second half."""
    from repro.serve.engine import ServeEngine
    orig = ServeEngine._run_window

    def half(self, selected):
        k = max(len(selected) // 2, 1)
        orig(self, selected[:k])
        for (t, _, _), (u, _, _) in zip(selected[k:],
                                        itertools.cycle(selected[:k])):
            t.result, t.window, t.via = u.result, u.window, "exec"

    monkeypatch.setattr(ServeEngine, "_run_window", half)
    _, out = tiny.run(tiny.READ_CELL)
    assert not out["correct"]
    assert out["checks"]["read_rows_wrong"]["value"] > 0


def test_write_that_leaves_state_unchanged_fails(monkeypatch):
    """``apply_writes`` acknowledges free slots and changes nothing."""
    from repro.core.views import BatchResult, GraphSession

    def unchanged(self, batch):
        _, edges = self._reserve_edge_slots(self.g, len(batch.edge_creates))
        _, nodes, _ = self._reserve_node_slots(self.g,
                                               len(batch.node_creates))
        return BatchResult(np.asarray(edges, np.int32),
                           np.asarray(nodes, np.int32))

    monkeypatch.setattr(GraphSession, "apply_writes", unchanged)
    _, out = tiny.run(tiny.WRITE_CELL)
    assert not out["correct"]
    assert out["checks"]["base_items_wrong"]["value"] > 0
