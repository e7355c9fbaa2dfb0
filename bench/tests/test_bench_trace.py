"""The trace reduction (bench/lib/trace.py) against a small trace recorded
on the CPU (data/record_cpu_trace.py) and against hand-made events."""
import os

import numpy as np
import pytest

from bench.lib import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "cpu_window.xplane.pb")
STEPS = 5  # the recording ran the program five times inside the window
OPS = ("wrapped_sine", "dot_general.1")  # the program's two operations


@pytest.fixture(scope="module")
def planes():
    return tr.load(DATA)


@pytest.fixture(scope="module")
def window(planes):
    return tr.window(tr.host_events(planes))


@pytest.fixture(scope="module")
def ops(planes, window):
    """The program's operations, which the CPU backend runs on its
    executor threads (a TPU trace has them on a device plane)."""
    evs = [e for p in planes for line, es in p.lines.items()
           if line.startswith("tf_XLAPjRtCpuClient") for e in es
           if e.name in OPS]
    return tr.clip(evs, *window)


def test_window_span_holds_every_step(planes, window):
    lo, hi = window
    steps = [e for e in tr.host_events(planes) if e.name == "step"]
    assert hi > lo and len(steps) == STEPS
    assert all(lo <= s.start_ns and s.end_ns <= hi for s in steps)


def test_each_operation_ran_once_per_step(ops):
    assert {n: sum(e.name == n for e in ops) for n in OPS} == \
        {n: STEPS for n in OPS}


def test_busy_union_matches_a_microsecond_grid(ops, window):
    lo, hi = window
    grid = np.zeros(int((hi - lo) // 1000) + 2, bool)
    for e in ops:
        grid[int((e.start_ns - lo) // 1000):int((e.end_ns - lo) // 1000)] = True
    busy = tr.busy_ns(ops, lo, hi)
    assert busy == pytest.approx(grid.sum() * 1000.0, abs=1000.0 * len(ops))
    assert 0 < busy < hi - lo


def test_idle_share_is_the_gaps(ops, window):
    lo, hi = window
    gaps = tr.idle_gaps(ops, lo, hi)
    assert sum(t - s for s, t in gaps) + tr.busy_ns(ops, lo, hi) == \
        pytest.approx(hi - lo)
    assert [t - s for s, t in gaps] == sorted((t - s for s, t in gaps),
                                              reverse=True)


def test_durations_by_name_and_per_step(ops):
    by_name = tr.sum_by_name(ops)
    for name in OPS:
        durs = [e.dur_ns for e in ops if e.name == name]
        assert by_name[name] == pytest.approx(sum(durs))
        assert by_name[name] / STEPS == pytest.approx(np.mean(durs))


def test_merge_of_nested_overlapping_and_disjoint_events():
    E = tr.Event
    evs = [E("a", 0, 10), E("b", 2, 3), E("c", 5, 15), E("d", 20, 25)]
    assert tr.merged(evs) == [(0, 15), (20, 25)]
    assert tr.busy_ns(evs, 0, 30) == 20
    assert tr.busy_ns(evs, 8, 22) == 9
    assert tr.idle_gaps(evs, 0, 30) == [(15, 20), (25, 30)]
    assert tr.idle_gaps(evs, -4, 30)[-1] == (-4, 0)


def _view(ops, modules, units=2):
    return tr.TraceView(lo=0, hi=1e9, ops=ops, modules=modules,
                        host=[tr.Event("busy host", 7e8, 9e8)], units=units,
                        counts={}, peaks={})


def test_view_averages_over_chips():
    E = tr.Event
    chip0 = [E("fusion", 0, 5e8), E("collective-permute-done", 5e8, 6e8)]
    chip1 = [E("fusion", 0, 3e8)]
    mods = [[E("jit_step(1)", 0, 6e8)], [E("jit_step(1)", 0, 3e8)]]
    v = _view([chip0, chip1], mods)
    assert v.chips == 2 and v.window_s == pytest.approx(1.0)
    assert v.busy_s() == pytest.approx((0.6 + 0.3) / 2)
    assert v.idle_pct() == pytest.approx(55.0)
    assert v.module_s("jit_step") == (pytest.approx(0.45), 1)
    assert v.op_s("collective-permute") == pytest.approx(0.05)
    b = v.breakdown()
    assert b["device_ops"][0] == ["fusion", pytest.approx(0.4)]
    assert b["idle_gaps"] == [["busy host", pytest.approx(0.4)]]
