"""Trace reduction: interval arithmetic on hand-made events, and the
whole reduction on a small trace recorded on a TPU v5e
(``record_trace.py``)."""
from __future__ import annotations

import pathlib
import re

import pytest

import counts
import tracereduce as tr
from conftest import load

DATA = pathlib.Path(__file__).resolve().parent / "data" / "small_trace"


def test_merge_intersect_subtract():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert tr.intersect([(0, 3), (5, 9)], [(2, 6)]) == [(2, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 6)]) == [(0, 2), (3, 5),
                                                       (6, 10)]
    assert tr.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert tr.total([(0, 3), (5, 9)]) == 7


def test_busy_exposed_and_matching():
    window = [(0, 100)]
    ops = [("fusion.1", 0, 30), ("fedagg_kernel", 20, 40),
           ("all-reduce.3", 35, 60), ("fusion.2", 50, 55),
           ("fusion.3", 90, 120)]
    # Busy: [0, 60) and [90, 100) inside the window.
    assert tr.busy_ns(ops, window) == 70
    assert tr.matching_ns(ops, "fedagg", window) == 20
    # The all-reduce runs alone in [40, 50) and [55, 60); a loop op
    # that spans it does not hide it.
    assert tr.exposed_ns(ops, "all-reduce", window) == 15
    loop = [("%while.1 = (f32[4]) while(%t)", 0, 100)] + ops
    assert tr.exposed_ns(loop, "all-reduce", window) == 15
    assert tr.exposed_ns(ops, "no-such-op", window) == 0


def test_leaves_and_short_names():
    ops = [("%while.3 = (f32[4]) while(%t)", 0, 100),
           ("%fusion.1 = f32[4]{0:T(128)} fusion(%a)", 10, 20),
           ("%cond.2 = f32[4] conditional(%p)", 30, 60),
           ("%fedagg_op.1 = f32[8]{0:T(1024)S(1)} custom-call(%w, %x)",
            35, 50)]
    assert [n for n, _, _ in tr.leaves(ops)] == [ops[1][0], ops[3][0]]
    assert tr.short_name(ops[3][0]) == "%fedagg_op.1 f32[8] custom-call"
    assert tr.short_name(ops[1][0]) == "%fusion.1 f32[4] fusion"


def test_top_ops_and_idle_gaps():
    window = [(0, 100)]
    dev = [("a", 0, 10), ("b", 10, 40), ("a", 60, 70)]
    assert tr.top_ops([dev, dev], window) == [["b", 30e-9], ["a", 20e-9]]
    host = [("bench.episode", 0, 100), ("plan_round", 40, 60)]
    gaps = tr.idle_gaps(dev, window, host)
    assert gaps[0] == ["bench.episode @0.000s", 30e-9]      # [70, 100)
    assert gaps[1] == ["plan_round @0.000s", 20e-9]          # [40, 60)


@pytest.fixture(scope="module")
def small():
    return tr.TraceView.load(str(DATA)), load(DATA / "facts.json")


def test_recorded_trace_window_and_busy(small):
    view, facts = small
    assert list(view.devices) == [0]
    # The window holds two folds, a matrix product and a 50 ms sleep.
    assert view.window_s >= facts["sleep_s"]
    busy = view.busy_s()
    assert 0 < busy < view.window_s - 0.9 * facts["sleep_s"]
    gaps = view.breakdown()["idle_gaps"]
    assert gaps[0][1] >= 0.9 * facts["sleep_s"]


def test_recorded_trace_fold_kernel(small):
    import importlib.util
    view, facts = small
    spec = importlib.util.spec_from_file_location(
        "fold_roofline", pathlib.Path(counts.__file__).parent / "metrics"
        / "fold_roofline.py")
    metric = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(metric)
    kernel = [op for op in view.devices[0]
              if re.search(metric.KERNEL, op[0])]
    # One kernel event per fold, and nothing that only reads its output.
    assert len(kernel) == facts["folds"]
    assert view.op_s(metric.KERNEL) > 0
    ops = [name for name, _ in view.breakdown()["device_ops"]]
    assert any(name.startswith("%fedagg_op") for name in ops)
