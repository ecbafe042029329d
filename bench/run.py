#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one JSON result line.

    python bench/run.py --workload paper-5x8.fedhap --seed 7 \
        --seconds 30 --trace 0

Everything the cell needs is found by name: the cell in
``BENCHMARK.json``, its configuration in ``bench/configs/<config>.json``,
its traffic in ``bench/traffic/<traffic>.json``, the family of the
executor call it drives in ``bench/families/<family>.py``, the limits of
its correctness check in ``bench/limits/<cell>.json``, each per-layer
metric's reader in ``bench/metrics/<metric>.py``, and the two halves of
the plain reference (``parts.py``): the model the configuration trains
in ``bench/payloads/<model.kind>.py`` and its data in
``bench/datasets/<sim.dataset>.py``. An unknown name exits before any
chip work, naming those there are.

A run:

1. refuses anything but a TPU with at least the cell's chips (exit 3,
   no result);
2. set-up: builds one ``RoundEngine`` and drives it through the traffic's
   probe episodes, which compile and warm every program the window uses
   and record the first update for the correctness check;
3. the window: whole episodes of ``RoundEngine.run`` back to back, each
   ended by ``block_until_ready`` on the final params, stopping before
   an episode that would end past ``--seconds`` at the last one's pace.
   A backend compile inside the window fails the run (exit 4, no
   result). With ``--trace 1`` the window is one profiled episode and
   the result carries the per-layer metrics instead of the end-to-end
   ones;
4. frees the program's state and replays the probes and the window's
   last episode, every call of it, through the plain reference
   (``reference.py``), which also checks the calls' plan tensors against
   the configuration; each compared number and its limit are printed
   last on standard error and last in the result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import counts  # noqa: E402
import numpy as np  # noqa: E402
import parts  # noqa: E402

# Leaves whose first update in the reference is under this share of the
# median leaf's are left out of the update comparisons: they move by
# round-off alone.
STILL_LEAF = 1e-3
CHECKS = ("init_gap", "update1_gap", "change_gap", "acc_gap", "plan_faults")


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


class RunFailed(RuntimeError):
    """The run broke a rule of the measurement; it prints no result."""


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------ the cell
@dataclasses.dataclass
class Cell:
    name: str
    bench_dir: pathlib.Path
    chips: int
    config: dict
    traffic: dict
    limits: dict
    family: types.ModuleType
    payload: types.ModuleType
    end_to_end: list
    per_layer: list


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def for_cell(entries: list, cell: str) -> list:
    return [m for m in entries if cell in m.get("workloads", [cell])]


def cell_names(bench_dir: pathlib.Path) -> list[str]:
    return [w["name"] for w in
            load_json(bench_dir.parent / "BENCHMARK.json")["workloads"]]


def load_cell(bench_dir: pathlib.Path, name: str) -> Cell:
    spec = load_json(bench_dir.parent / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; the benchmark has "
                         f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(bench_dir.parent / configs[w["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    # The reference finds its model and data by these names: an unknown
    # one exits here, before any chip work.
    parts.dataset(config["sim"]["dataset"], bench_dir)
    return Cell(
        name=name, bench_dir=bench_dir, chips=int(w["chips"]), config=config, traffic=traffic,
        limits=load_json(bench_dir / "limits" / f"{name}.json"),
        family=parts.load_module(bench_dir / "families"
                           / f"{traffic['family']}.py"),
        payload=parts.payload(config["model"]["kind"], bench_dir),
        end_to_end=for_cell(spec["end_to_end"], name),
        per_layer=for_cell(spec["per_layer"], name))


def find_chips(n: int) -> list:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0] is {devices[0].platform}")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} TPU chips, found {len(devices)}")
    return devices[:n]


# -------------------------------------------------------- measurement
class CompileClock:
    """Backend compile seconds and count, and persistent-cache hits,
    from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def to_host(tree) -> dict:
    """The leaves of a pytree on the host, by their paths."""
    from reference import leaves
    return {k: np.asarray(v, np.float64) for k, v in leaves(tree).items()}


def add_work(acc: dict, w: dict) -> None:
    for k, v in w.items():
        acc[k] = acc.get(k, [] if isinstance(v, list) else 0) + v


class Recorder:
    """Wraps the executor call of the cell's family. Counts the work each
    call needed and keeps the plan tensors of the current episode's
    calls; inside a probe it also keeps host copies of the params each
    recorded call took and returned."""

    def __init__(self, family: types.ModuleType, executor, chips: int):
        self.family, self.executor, self.chips = family, executor, chips
        self.orig = getattr(executor, family.METHOD)
        setattr(executor, family.METHOD, self._call)
        self.work: dict = {}
        self.feeds: list = []
        self.probe_state = None

    def _call(self, *args, **kw):
        feed = self.family.capture(args)
        add_work(self.work, self.family.work(feed, self.chips))
        self.feeds.append(feed)
        p = self.probe_state
        if p is None or len(p["outs"]) >= p["calls"]:
            return self.orig(*args, **kw)
        if p["p0"] is None:
            p["p0"] = to_host(self.family.params_in(args))
        out = self.orig(*args, **kw)
        p["outs"].append(to_host(self.family.params_out(out)))
        return out

    def episode(self, eng, max_rounds: int):
        self.feeds = []
        return episode(eng, max_rounds)

    def probe(self, eng, max_rounds: int, calls: int) -> dict:
        """A probe episode: the first ``calls`` calls' plan tensors and
        params, and the episode's evals."""
        self.probe_state = {"calls": calls, "p0": None, "outs": []}
        try:
            res = self.episode(eng, max_rounds)
        finally:
            p, self.probe_state = self.probe_state, None
        p["feeds"] = self.feeds[:calls]
        p["history"] = res.history
        return p

    def last_episode(self, res) -> dict:
        """The record of the episode that ended in ``res``: every call's
        plan tensors, its final params and its evals. Read once the
        window has closed."""
        return {"p0": None, "feeds": self.feeds,
                "outs": [to_host(res.params)], "history": res.history}

    def unwrap(self) -> None:
        setattr(self.executor, self.family.METHOD, self.orig)


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def episode(eng, max_rounds: int):
    """One episode: the scenario replayed from the seed's initial model
    for ``max_rounds`` updates, ended on the device."""
    import jax
    eng.cfg = dataclasses.replace(eng.cfg, max_rounds=max_rounds)
    res = eng.run()
    jax.block_until_ready(res.params)
    return res


class GcClock:
    """Host seconds and passes of Python's garbage collector, by
    generation, while it is registered."""

    def __init__(self):
        self.seconds, self.passes, self._t = [0.0] * 3, [0] * 3, None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            g = info["generation"]
            self.seconds[g] += time.perf_counter() - self._t
            self.passes[g] += 1


def free_device_state() -> None:
    import jax
    gc.collect()
    jax.clear_caches()
    for a in jax.live_arrays():
        a.delete()


# --------------------------------------------------------- correctness
def norm_gap(dp: dict, dr: dict, keep: list) -> float:
    """Worst leaf's gap between the program's and the reference's
    update norms, over the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    nr = {k: float(np.linalg.norm(dr[k])) for k in dr}
    med = float(np.median(list(nr.values())))
    return max(abs(float(np.linalg.norm(dp[k])) - nr[k]) / max(nr[k], med)
               for k in keep)


def diff(a: dict, b: dict) -> dict:
    return {k: a[k] - b[k] for k in a}


def replay(cell: Cell, records: list, ref) -> tuple:
    """The reference's replay of each recorded episode (the probes, then
    the window's last episode), each from the seed's initial model: its
    initial model, its state after each recorded call, its evals
    ``{(record, update): accuracy}`` and how many rules the recorded
    plan tensors break."""
    from reference import to_host as ref_host
    g0 = ref.init()
    outs, evals, plan_faults = [], {}, 0
    for i, r in enumerate(records):
        state, seen, row = {"g": g0, "g0": g0, "updates": 0}, {}, []
        for feed in r["feeds"]:
            plan_faults += cell.family.plan_faults(ref, feed, seen)
            for u, acc in cell.family.replay(ref, state, feed):
                evals[(i, u)] = acc
            row.append(ref_host(state["g"]))
        outs.append(row)
    return ref_host(g0), outs, evals, plan_faults


def in_programs_place(records: list, ref_init: dict, ref_outs: list,
                      ref_evals: dict, plan_faults: int) -> list:
    """A reference's replay in the shape of the program's records, so it
    can be compared in the program's place (the control)."""
    return [{"p0": ref_init, "feeds": r["feeds"], "outs": outs,
             "history": [(0.0, u, acc) for (i, u), acc in
                         sorted(ref_evals.items()) if i == n]}
            for n, (r, outs) in enumerate(zip(records, ref_outs))]


def compare(cell: Cell, records: list, ref_init: dict, ref_outs: list,
            ref_evals: dict, plan_faults: int) -> dict:
    """The numbers compared. ``records[0]`` is the first probe, whose
    first call is the first update; ``records[-1]`` is the window's last
    episode, whose change from the initial model is compared whole."""
    n_eval = int(cell.config["sim"]["eval_samples"])
    first = records[0]
    init_gap = max(float(np.max(np.abs(r["p0"][k] - ref_init[k])))
                   for r in records if r["p0"] is not None
                   for k in ref_init)
    dr1 = diff(ref_outs[0][0], ref_init)
    n1 = {k: float(np.linalg.norm(v)) for k, v in dr1.items()}
    med = float(np.median(list(n1.values())))
    keep = [k for k in n1 if n1[k] >= STILL_LEAF * med]
    update1 = norm_gap(diff(first["outs"][0], first["p0"]), dr1, keep)
    change = norm_gap(diff(records[-1]["outs"][-1], first["p0"]),
                      diff(ref_outs[-1][-1], ref_init), keep)
    acc_gap = 0.0
    for i, r in enumerate(records):
        got = {u: acc for _, u, acc in r["history"]}
        want = {u: acc for (j, u), acc in ref_evals.items() if j == i}
        # Every call of the window's episode is replayed: an eval that
        # only one side made is a gap of its own.
        if r is records[-1] and set(got) != set(want):
            acc_gap = math.inf
        for u in set(got) & set(want):
            acc_gap = max(acc_gap, abs(got[u] - want[u]) * n_eval)
    return {"init_gap": init_gap, "update1_gap": update1,
            "change_gap": change, "acc_gap": acc_gap,
            "plan_faults": float(plan_faults)}


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    checks, ok = {}, True
    for name in CHECKS:
        v, lim = values[name], limits.get(name)
        if lim is not None:
            ok &= bool(math.isfinite(v) and v <= lim)
        checks[name] = {"value": v, "limit": lim}
    return ok, checks


def per_layer_metrics(cell: Cell, ctx) -> dict:
    """Each per-layer metric of the cell from its own reader; a reader
    that finds nothing to read returns None and the metric is left
    out."""
    out = {}
    for m in cell.per_layer:
        reader = parts.load_module(cell.bench_dir / "metrics"
                                   / f"{m['name']}.py")
        v = reader.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


# ------------------------------------------------------------- the run
def set_up(cell: Cell, sim: dict) -> tuple:
    """Build the engine, wrap its executor call and drive it through
    the probe episodes. Returns ``(engine, recorder, probes, seconds
    the engine build took)``."""
    from repro.sim import RoundEngine, SimConfig
    model, probe = cell.config["model"], cell.traffic["probe"]
    t = time.perf_counter()
    eng = RoundEngine(SimConfig(**sim))
    engine_build_s = time.perf_counter() - t
    n_params = eng.trainer.model.count_params()
    if (n_params != model["params"]
            or n_params != cell.payload.params(model)):
        raise RunFailed(f"the program's model has {n_params} params, the "
                        f"configuration {model['params']}")
    rec = Recorder(cell.family, eng.executor, cell.chips)
    probes = [rec.probe(eng, n, probe["calls"]) for n in probe["episodes"]]
    short = [len(p["outs"]) for p in probes
             if len(p["outs"]) < probe["calls"]]
    if short:
        raise RunFailed(f"a probe episode made {short[0]} calls, fewer than "
                        f"the {probe['calls']} it records")
    # The heap that set-up leaves (traced and compiled programs) would
    # meet its first full collection in the window, a host stall of
    # seconds; collecting it here counts that cost in set-up. Later
    # collections run as in any process.
    gc.collect()
    return eng, rec, probes, engine_build_s


def sim_config(cell: Cell, seed: int) -> dict:
    """The ``SimConfig`` fields of the cell at ``seed``."""
    return {**cell.config["sim"], **cell.traffic["sim"], "seed": seed,
            "max_rounds": cell.traffic["episode"]["max_rounds"]}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices: list) -> dict:
    import jax
    from repro.launch.compile_cache import use_compile_cache
    from reference import Reference

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock()
    model = cell.config["model"]
    sim = sim_config(cell, seed)
    length = sim["max_rounds"]

    with span("bench.setup"):
        eng, rec, probes, engine_build_s = set_up(cell, sim)
    setup_compile_s, setup_compiles = clock.seconds, clock.compiles
    setup_s = time.perf_counter() - T_START
    say(f"set-up {setup_s!r} s: engine build {engine_build_s!r} s, "
        f"{setup_compiles} backend compiles {setup_compile_s!r} s, "
        f"{clock.cache_hits} persistent-cache hits")

    rec.work = {}
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    gc_clock = GcClock()
    gc.callbacks.append(gc_clock)
    eps = []
    t_window = time.perf_counter()
    while True:
        before = dict(rec.work)
        t = time.perf_counter()
        with span("bench.episode"):
            res = rec.episode(eng, length)
        took = time.perf_counter() - t
        done = rec.work.get("updates", 0) - before.get("updates", 0)
        accs = [a for _, _, a in res.history]
        eps.append({"seconds": took, "updates": done,
                    "ok": done >= length and bool(accs)
                    and all(math.isfinite(a) for a in accs)})
        if trace or time.perf_counter() - t_window + took > seconds:
            break
    window_s = time.perf_counter() - t_window
    gc.callbacks.remove(gc_clock)
    if trace:
        jax.profiler.stop_trace()
    in_window = clock.compiles - setup_compiles
    if in_window:
        raise RunFailed(f"{in_window} backend compiles inside the window")
    work = rec.work
    say(f"window {window_s!r} s: {len(eps)} episodes, {work.get('updates')} "
        f"updates, episode seconds {[e['seconds'] for e in eps]!r}; "
        f"garbage collector by generation: passes {gc_clock.passes}, "
        f"seconds {gc_clock.seconds!r}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    metrics = {}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        from tracereduce import TraceView
        tv = TraceView.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        peaks = load_json(cell.bench_dir / "peaks.json")
        if devices[0].device_kind not in peaks:
            raise RunFailed(f"no peaks for {devices[0].device_kind!r} in "
                            f"peaks.json")
        ctx = types.SimpleNamespace(
            trace=tv, work=work, chips=cell.chips, config=cell.config,
            sim=sim, model=model, counts=counts,
            peak=peaks[devices[0].device_kind],
            engine_build_s=engine_build_s, compile_s=setup_compile_s)
        metrics = per_layer_metrics(cell, ctx)
        device["busy_s"] = tv.busy_s(cell.chips)
        device["window_s"] = tv.window_s
        breakdown = tv.breakdown(cell.chips)
    else:
        e2e = {"updates_per_s": work.get("updates", 0) / window_s,
               "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    records = probes + [rec.last_episode(res)]
    rec.unwrap()
    del eng, res, rec
    free_device_state()
    t = time.perf_counter()
    ref = Reference(cell.config, seed, devices=devices)
    values = compare(cell, records, *replay(cell, records, ref))
    ok, checks = judge(values, cell.limits)
    say(f"reference {time.perf_counter() - t!r} s")
    failed = sum(not e["ok"] for e in eps)
    for name, c in checks.items():
        say(f"check {name} {c['value']!r} limit {c['limit']!r}")
    say(f"check failed_episodes {failed} limit 0")
    result = {"correct": ok and failed == 0, "attempted": len(eps),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(BENCH, args.workload)
    sys.path.insert(0, str(BENCH.parent / "src"))
    try:
        devices = find_chips(cell.chips)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          devices)
    except NoChip as e:
        say(f"{e}; nothing was run")
        return 3
    except RunFailed as e:
        say(f"run failed: {e}")
        return 4
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
