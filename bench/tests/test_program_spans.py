"""The readers of the program's own spans and counters: the trace
readers on hand-made trace views, the counter readers against a faked
``repro.obs``, and a CPU profiler trace of one tiny fedhap episode in
which the program's spans land inside the harness's window."""
from __future__ import annotations

import types

import jax
import pytest

import parts
import run
import tracereduce as tr
from conftest import make_checkout, tiny_cell
from repro import obs

MS = 1_000_000                      # nanoseconds


def reader(name: str):
    return parts.load_module(run.BENCH / "metrics" / f"{name}.py").read


def view() -> tr.TraceView:
    """A 100 ms window. ``sim.plan`` spans: one straddling the window's
    start, one inside, one after it. Chip 0 has overlapping ops."""
    host = [("bench.episode", 0, 100 * MS),
            ("sim.plan", -10 * MS, 5 * MS),
            ("sim.plan", 40 * MS, 60 * MS),
            ("exec.dispatch", 60 * MS, 61 * MS),
            ("sim.plan", 120 * MS, 130 * MS)]
    devices = {0: [("a", 0, 2 * MS), ("b", 45 * MS, 50 * MS),
                   ("c", 48 * MS, 55 * MS)],
               1: [("a", 0, 5 * MS), ("b", 50 * MS, 70 * MS)]}
    return tr.TraceView(devices, host, [(0, 100 * MS)])


def ctx(chips: int = 2, updates: int = 5, trace=None):
    return types.SimpleNamespace(trace=trace or view(), chips=chips,
                                 work={"updates": updates})


def test_plan_ms_counts_the_span_inside_the_window():
    # [0, 5) and [40, 60): 25 ms over 5 updates.
    assert reader("plan_ms_per_update")(ctx()) == pytest.approx(5.0)
    assert reader("plan_ms_per_update")(ctx(updates=0)) is None
    empty = tr.TraceView({0: []}, [("bench.episode", 0, MS)], [(0, MS)])
    assert reader("plan_ms_per_update")(ctx(trace=empty)) is None


def test_plan_stall_leaves_out_device_busy_time_per_chip():
    stall = reader("plan_stall_ms_per_update")
    # Chip 0 is busy in [0, 2) and [45, 55): 3 + 5 + 5 = 13 ms of the
    # plan are stalls; chip 1, busy in [0, 5) and [50, 70): 10 ms.
    assert stall(ctx(chips=1)) == pytest.approx(13 / 5)
    assert stall(ctx(chips=2)) == pytest.approx(11.5 / 5)
    assert stall(ctx()) <= reader("plan_ms_per_update")(ctx())
    idle = tr.TraceView({0: []}, view().host, [(0, 100 * MS)])
    assert stall(ctx(chips=1, trace=idle)) == pytest.approx(5.0)
    assert stall(ctx(trace=tr.TraceView({}, view().host,
                                        [(0, 100 * MS)]))) is None


@pytest.fixture
def fake_obs(monkeypatch):
    state = {"last": {}, "totals": {}}
    monkeypatch.setattr(obs, "last_run", lambda: dict(state["last"]))
    monkeypatch.setattr(obs, "totals", lambda: dict(state["totals"]))
    return state


def test_counter_readers_read_the_traced_run(fake_obs):
    # paper-5x8.fedhap's episode: two blocks of K=8, S=40, 54 x 32 rows.
    block = 8 * 40 * 1728 * 4 + 8 * 40 * 4 + 16
    fake_obs["last"] = {"exec.dispatches": 2, "updates": 16,
                        "exec.upload_bytes": 2 * block}
    c = ctx(updates=16)
    assert reader("dispatches_per_update")(c) == 0.125
    assert reader("upload_kib_per_update")(c) == pytest.approx(270.16,
                                                               abs=0.01)


def test_counter_readers_refuse_another_run(fake_obs):
    fake_obs["last"] = {"exec.dispatches": 2, "updates": 8,
                        "exec.upload_bytes": 1024}
    for name in ("dispatches_per_update", "upload_kib_per_update"):
        assert reader(name)(ctx(updates=16)) is None
        assert reader(name)(ctx(updates=8)) is not None
    fake_obs["last"] = {}
    for name in ("dispatches_per_update", "upload_kib_per_update"):
        assert reader(name)(ctx(updates=16)) is None


def test_program_build_reads_the_process_totals(fake_obs):
    assert reader("program_build_s")(ctx()) is None
    fake_obs["totals"] = {"exec.build.seconds": 3.5,
                          "exec.dispatch.seconds": 1.0}
    assert reader("program_build_s")(ctx()) == 3.5


def test_spans_land_inside_the_window_of_a_cpu_trace(tmp_path):
    bench = make_checkout(tmp_path)
    tiny_cell(bench, "tiny-r.fedhap", "paper-5x8.fedhap")
    cell = run.load_cell(bench, "tiny-r.fedhap")
    from repro.sim import RoundEngine, SimConfig
    sim = run.sim_config(cell, 2**31 + 5)
    eng = RoundEngine(SimConfig(**sim))
    run.episode(eng, sim["max_rounds"])          # builds every program
    trace_dir = tmp_path / "trace"
    jax.profiler.start_trace(str(trace_dir))
    with run.span(tr.WINDOW_SPAN):
        run.episode(eng, sim["max_rounds"])
    jax.profiler.stop_trace()
    tv = tr.TraceView.load(str(trace_dir))
    assert tv.window_s > 0
    inside = {n for n, s, e in tv.host
              if tr.intersect([(s, e)], tv.window) == [(s, e)]}
    assert {"sim.plan", "exec.dispatch", "exec.sync"} <= inside
    assert "exec.build" not in inside
    c = types.SimpleNamespace(trace=tv, chips=1,
                              work={"updates": sim["max_rounds"]})
    assert reader("plan_ms_per_update")(c) > 0
    assert reader("dispatches_per_update")(c) \
        == obs.last_run()["exec.dispatches"] / sim["max_rounds"]
