"""Trace reduction: exact answers on a hand-made trace, and sound ones on a
trace recorded on a v5e (``record_trace.py``)."""
import json
from pathlib import Path

import pytest

import traces as T

DATA = Path(__file__).resolve().parent / "data"


def _made():
    ops = T.Spans.of([("fusion", 10, 20), ("reduce_fusion", 30, 60),
                      ("copy", 55, 70), ("while", 30, 70)])
    host = T.Spans.of([("bench.window", 0, 100), ("bench.step", 0, 50),
                       ("PjitFunction(f)", 15, 35)])
    return T.Trace(devices=[ops], host=host, window=(0.0, 100.0))


def test_busy_is_the_union_of_operations():
    tr = _made()
    assert T.busy_s(tr) == pytest.approx(50e-9)
    assert T.idle_share_pct(tr) == pytest.approx(50.0)


def test_leaf_seconds_leave_containers_out():
    leaves = T.leaf_seconds(_made())
    assert "while" not in leaves
    assert leaves == pytest.approx({"fusion": 10e-9, "reduce_fusion": 30e-9,
                                    "copy": 15e-9})


def test_idle_gaps_go_to_the_innermost_host_span():
    gaps = T.idle_gaps(_made())
    assert gaps == pytest.approx({"bench.step": 10e-9, "PjitFunction(f)": 10e-9,
                                  "no host span": 30e-9})


def test_window_clips_operations():
    tr = _made()
    tr.window = (15.0, 40.0)
    assert T.busy_s(tr) == pytest.approx(15e-9)        # [15, 20] and [30, 40]
    assert tr.window_s == pytest.approx(25e-9)


def test_op_names():
    assert T.op_name("%reduce_fusion.3 = f32[80]{0} fusion(x)") == "reduce_fusion.3"
    assert T.base_name("vmap_jit_screen_pallas__.7") == "vmap_jit_screen_pallas__"


TRACE = str(DATA / "sinkhorn.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return T.load(TRACE), json.loads((DATA / "sinkhorn.json").read_text())


def test_recorded_trace_has_the_device_and_the_window(recorded):
    tr, _ = recorded
    assert len(tr.devices) == 1 and tr.devices[0].start.size > 100
    assert 0 < tr.window_s < 10
    busy = T.busy_s(tr)
    assert 0 < busy <= tr.window_s
    assert 0 <= T.idle_share_pct(tr) < 100


def test_recorded_time_per_iteration(recorded):
    """Busy time covers every leaf operation, and the loop's own time
    between them (the ``while`` container counts as busy, not as a leaf)."""
    tr, facts = recorded
    assert facts["iters"] == 200 and facts["device_kind"] == "TPU v5 lite"
    leaves = T.leaf_seconds(tr)
    assert "while" not in leaves
    assert 0 < max(leaves.values()) <= T.busy_s(tr) <= tr.window_s
    assert 1e-7 < T.busy_s(tr) / facts["iters"] < 1e-3


def test_recorded_breakdown(recorded):
    tr, _ = recorded
    red = T.reduce(TRACE)
    assert 0 < len(red.breakdown["device_ops"]) <= 10
    assert sum(s for _, s in red.breakdown["idle_gaps"]) <= red.window_s
    idle = sum(T.idle_gaps(tr).values())
    assert red.busy_s + idle == pytest.approx(red.window_s, rel=1e-6)
