"""Geometry-engine benchmark: batched grids vs per-pair Python, delay
tables vs re-propagation, routing tables, and a mega-constellation
scenario sweep.

Four sections, all recorded to ``BENCH_sim.json`` (schema documented in
``benchmarks/README.md``) so the perf trajectory is tracked across PRs:

- **grid_build** — wall time of the batched ``visibility_mask`` (one
  stacked-ephemeris propagation + broadcasted elevation test) vs the
  per-pair ``visibility_mask_pairwise`` reference, on a 20x40 Walker
  shell by default (the acceptance scenario: batched must be >=5x).
- **delay_table** — eager SHL-delay-table build time plus lookup
  latency (``RoundEngine.shl_delay`` / batched ``shl_delays``) vs the
  per-call re-propagating reference.
- **routing** — the ISL routing subsystem: contact-graph (LoS grid +
  edge-next table) build times up to a 20x40 shell, batched
  earliest-arrival search vs the per-edge Python reference (checked
  allclose), the scheduling-only throughput of the routed
  ``fedhap_async`` event loop vs fedhap rounds, and the stitched
  windowed router vs the single-graph oracle on mega shells
  (``stitched_sweep``: build/route costs checked allclose + buffered
  scheduling events/s over the window chain), and a Starlink-scale
  ``mega_sweep`` (72x22): dense all-pairs window build vs the sparse
  intra-plane CSR table, frontier earliest-arrival, and run-batched
  buffered scheduling events/s.
- **sim_fused** — the fused plan-ahead driver vs the per-round /
  per-event reference loop (local SGD excluded) for fedhap,
  fedhap_async, and fedhap_buffered on the paper 5x8 shell and a 10x20
  shell: K planned rounds (or cycle events) batched into schedule
  tensors and executed as one device dispatch.
- **sim_sharded** — 1-vs-D device scaling of the sharded fused
  megastep (``SimConfig.data_shards`` -> shard_map over the satellite
  axis, aggregation through the production mesh round's weighted
  psum): fedhap on a ``grid:3x6`` gateway grid over a 20x40 shell and
  a two-shell ``shells:`` constellation, over every device of this one
  process (skipped with one device). Real local SGD included —
  sharding accelerates the train+fold megastep itself.
- **sweep** — ``haps:N`` / ``grid:RxC`` station scenarios crossed with
  large Walker shells: records grid-build time and scheduler-only
  FedHAP rounds/sec (local SGD excluded, as in ``sim_wallclock``).

Usage:
  PYTHONPATH=src python -m benchmarks.bench_geometry            # full
  PYTHONPATH=src python -m benchmarks.bench_geometry --smoke    # CI tier
  PYTHONPATH=src python -m benchmarks.bench_geometry --sim-wallclock
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import time

import numpy as np

from repro.orbits import (
    WalkerConstellation,
    visibility_mask,
    visibility_mask_pairwise,
)
from repro.orbits.routing import (
    build_contact_graph,
    earliest_arrival,
    earliest_arrival_reference,
)
from repro.launch.compile_cache import use_compile_cache
from repro.sim import SimConfig
from repro.sim.engine import RoundEngine, _make_stations

DEFAULT_OUT = pathlib.Path(__file__).resolve().parent.parent / "BENCH_sim.json"

# Tiny dataset settings: these benches measure geometry + scheduling,
# not SGD, so the FL side is kept as small as the engine allows.
_SIM_LITE = dict(model_kind="mlp", num_samples=4000, eval_samples=500,
                 iid=True)


def _scenario_cfg(stations: str, shell: tuple[int, int],
                  horizon_h: float, step_s: float) -> SimConfig:
    return SimConfig(strategy="fedhap", stations=stations,
                     num_orbits=shell[0], sats_per_orbit=shell[1],
                     horizon_h=horizon_h, time_step_s=step_s, **_SIM_LITE)


def bench_grid_build(stations: str, shell: tuple[int, int],
                     horizon_h: float, step_s: float,
                     check: bool = True) -> dict:
    """Batched vs per-pair visibility-grid build on one scenario."""
    sts = _make_stations(stations)
    con = WalkerConstellation(shell[0], shell[1])
    ts = np.arange(int(horizon_h * 3600 / step_s) + 2) * step_s
    t0 = time.perf_counter()
    batched = visibility_mask(sts, con, ts)
    batched_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    pairwise = visibility_mask_pairwise(sts, con, ts)
    pairwise_s = time.perf_counter() - t0
    if check:
        assert (batched == pairwise).all(), "batched grid != per-pair grid"
    return {
        "stations": stations, "shell": f"{shell[0]}x{shell[1]}",
        "n_stations": len(sts), "n_sats": len(con), "T": len(ts),
        "batched_s": round(batched_s, 4),
        "pairwise_s": round(pairwise_s, 4),
        "speedup": round(pairwise_s / batched_s, 2),
    }


def bench_delay_table(stations: str, shell: tuple[int, int],
                      horizon_h: float, step_s: float,
                      n_queries: int = 2000) -> dict:
    """Delay-table build + lookup cost vs the re-propagating reference."""
    cfg = _scenario_cfg(stations, shell, horizon_h, step_s)
    t0 = time.perf_counter()
    eng = RoundEngine(cfg)
    init_s = time.perf_counter() - t0
    T = len(eng.grid_t)
    rng = np.random.default_rng(0)
    st_i = rng.integers(0, len(eng.stations), n_queries)
    sat_i = rng.integers(0, eng.n_sats, n_queries)
    t_i = rng.integers(0, T, n_queries)
    times = eng.grid_t[t_i]

    t0 = time.perf_counter()
    for a, b, t in zip(st_i, sat_i, times):
        eng.shl_delay(int(a), int(b), float(t))
    lookup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    gathered = eng.shl_delays(st_i, sat_i, t_i)
    gather_s = time.perf_counter() - t0
    ref_n = min(n_queries, 200)       # the reference path is slow
    t0 = time.perf_counter()
    refs = [eng.shl_delay_reference(int(a), int(b), float(t))
            for a, b, t in zip(st_i[:ref_n], sat_i[:ref_n], times[:ref_n])]
    ref_s = (time.perf_counter() - t0) * (n_queries / ref_n)
    assert np.allclose(gathered[:ref_n], refs, rtol=1e-5)
    return {
        "stations": stations, "shell": f"{shell[0]}x{shell[1]}",
        "T": T, "eager_table": eng.shl_table is not None,
        "engine_init_s": round(init_s, 4),
        "lookup_us": round(lookup_s / n_queries * 1e6, 3),
        "gather_us": round(gather_s / n_queries * 1e6, 3),
        "reference_us": round(ref_s / n_queries * 1e6, 3),
        "speedup": round(ref_s / lookup_s, 2),
    }


def bench_routing_build(shell: tuple[int, int], horizon_h: float,
                        step_s: float, n_params: int = 100_000) -> dict:
    """Contact-graph compile cost for one shell: stacked propagation,
    chunked all-pairs LoS grid, and the vectorized edge-next sweep."""
    con = WalkerConstellation(shell[0], shell[1])
    ts = np.arange(int(horizon_h * 3600 / step_s) + 2) * step_s
    t0 = time.perf_counter()
    pos = con.positions_eci(ts)
    propagate_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    graph = build_contact_graph(con, ts, n_params, positions=pos)
    build_s = time.perf_counter() - t0
    mb = (graph.isl_vis.nbytes + graph.edge_next.nbytes) / 2**20
    return {
        "shell": f"{shell[0]}x{shell[1]}", "n_sats": len(con),
        "T": len(ts), "horizon_h": horizon_h,
        "propagate_s": round(propagate_s, 4),
        "build_s": round(build_s, 4),
        "table_mb": round(mb, 1),
        "isl_density": round(float(graph.isl_vis.mean()), 4),
    }


def bench_earliest_arrival(shell: tuple[int, int] = (5, 8),
                           horizon_h: float = 6.0, step_s: float = 60.0,
                           n_ref_sources: int = 4) -> dict:
    """Batched all-sources earliest-arrival vs the per-edge Python
    reference (must agree allclose — the routing acceptance check)."""
    con = WalkerConstellation(shell[0], shell[1])
    ts = np.arange(int(horizon_h * 3600 / step_s) + 2) * step_s
    graph = build_contact_graph(con, ts, 100_000)
    S = len(con)
    t0 = time.perf_counter()
    arr = earliest_arrival(graph, np.arange(S), 0.0)
    batched_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for src in range(n_ref_sources):
        ref = earliest_arrival_reference(graph, src, 0.0)
        assert np.allclose(np.nan_to_num(arr[src], posinf=1e18),
                           np.nan_to_num(ref, posinf=1e18),
                           rtol=1e-9, atol=1e-6), \
            "batched earliest-arrival != per-edge reference"
    reference_s = (time.perf_counter() - t0) * (S / n_ref_sources)
    return {
        "shell": f"{shell[0]}x{shell[1]}", "n_sats": S, "T": len(ts),
        "sources": S,
        "batched_s": round(batched_s, 4),
        "reference_s": round(reference_s, 4),
        "speedup": round(reference_s / batched_s, 2),
        "reachable_frac": round(float(np.isfinite(arr).mean()), 4),
    }


def bench_stitched_sweep(shell: tuple[int, int], horizon_h: float,
                         step_s: float, windows: int = 4,
                         rounds: int = 20, n_sources: int = 8) -> dict:
    """Stitched windowed routing vs the single-graph oracle on one
    mega shell: whole-horizon graph build cost vs lazy window builds,
    all-horizon earliest-arrival cost (checked allclose between the two
    — the PR-5 exactness acceptance), and the scheduling-only
    ``fedhap_buffered`` event throughput riding the stitched router
    (sink election + cross-plane routed exits, local SGD excluded)."""
    import dataclasses

    from repro.sim.strategies import get_strategy
    S = shell[0] * shell[1]
    T = int(horizon_h * 3600 / step_s) + 2
    # Budget sized for ~`windows` half-overlapping windows of the grid.
    W = max(32, (2 * T) // (windows + 1))
    cfg = dataclasses.replace(
        _scenario_cfg("two_hap", shell, horizon_h, step_s),
        strategy="fedhap_buffered", isl_grid_max_bytes=S * S * 3 * W)
    eng = RoundEngine(cfg)
    router = eng.contact_graph(0.0)

    t0 = time.perf_counter()
    oracle = eng.full_contact_graph()
    oracle_build_s = time.perf_counter() - t0
    srcs = np.linspace(0, S - 1, n_sources).astype(np.int64)
    t0 = time.perf_counter()
    arr_o = earliest_arrival(oracle, srcs, 0.0)
    oracle_route_s = time.perf_counter() - t0
    del oracle

    t0 = time.perf_counter()
    arr_s = earliest_arrival(router, srcs, 0.0)   # builds windows lazily
    stitched_cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    earliest_arrival(router, srcs, 0.0)           # windows now cached
    stitched_warm_s = time.perf_counter() - t0
    assert np.allclose(np.nan_to_num(arr_s, posinf=1e18),
                       np.nan_to_num(arr_o, posinf=1e18),
                       rtol=1e-9, atol=1e-6), \
        "stitched windowed routing != single-graph oracle"

    strat = get_strategy("fedhap_buffered")()

    def drive():
        st = strat.init_plan_state(eng, 0.0)
        n = 0
        while n < rounds:
            events = strat.plan_events(eng, st, rounds - n)
            if not events:
                break
            n += len(events)
        return n

    drive()                       # warm the window + election caches
    eng._sink_cache.clear()       # time steady-state pricing, not memo hits
    t0 = time.perf_counter()
    n = drive()
    sched_s = time.perf_counter() - t0
    return {
        "shell": f"{shell[0]}x{shell[1]}", "n_sats": S, "T": T,
        "horizon_h": horizon_h,
        "windows": len(router.window_starts(0.0)),
        "window_steps": eng._window_steps,
        "oracle_build_s": round(oracle_build_s, 4),
        "oracle_route_s": round(oracle_route_s, 4),
        "stitched_cold_s": round(stitched_cold_s, 4),
        "stitched_warm_s": round(stitched_warm_s, 4),
        "sched_rounds": n,
        "sched_rps": round(n / sched_s, 2),
    }


def bench_mega_sweep(shell: tuple[int, int], horizon_h: float,
                     step_s: float = 60.0, events: int = 30,
                     n_sources: int = 4) -> dict:
    """Starlink-scale routed scheduling on one shell: dense all-pairs
    window build vs the sparse intra-plane CSR build (the table the
    batched sink election actually routes), sparse-frontier
    earliest-arrival over the dense window, and the scheduling-only
    ``fedhap_buffered`` event throughput (run-batched plan loop: one
    block-diagonal election + one multi-source exit sweep per run of
    arrivals). Routed exit hop depth is recorded as a diagnostic."""
    import dataclasses

    from repro.sim.strategies import get_strategy
    S = shell[0] * shell[1]
    cfg = dataclasses.replace(
        _scenario_cfg("two_hap", shell, horizon_h, step_s),
        strategy="fedhap_buffered")
    eng = RoundEngine(cfg)

    t0 = time.perf_counter()
    g_dense = eng._window_graph(0)
    dense_build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    g_csr = eng._intra_window(0)
    csr_build_s = time.perf_counter() - t0
    dense_mb = (g_dense.isl_vis.nbytes + g_dense.edge_next.nbytes) / 2**20
    csr_mb = (g_csr.nbr_vis.nbytes + g_csr.nbr_next.nbytes) / 2**20

    srcs = np.linspace(0, S - 1, n_sources).astype(np.int64)
    t0 = time.perf_counter()
    arr = earliest_arrival(g_dense, srcs, 0.0)
    route_s = time.perf_counter() - t0

    strat = get_strategy("fedhap_buffered")()

    def drive():
        st = strat.init_plan_state(eng, 0.0)
        n = 0
        while n < events:
            evs = strat.plan_events(eng, st, events - n)
            if not evs:
                break
            n += len(evs)
        return n

    drive()                       # warm the window + election caches
    eng._sink_cache.clear()       # time steady-state pricing, not memo hits
    t0 = time.perf_counter()
    n = drive()
    sched_s = time.perf_counter() - t0

    nl = min(4, shell[0])
    el = eng.elect_sinks_batch(range(nl), [eng.train_time()] * nl)
    hops = []
    for sk, dv in zip(el.sinks, el.delivery):
        if np.isfinite(dv):
            _, _, hop = eng.route_exit_plan(int(sk), float(dv))
            hops.append(max(0, len(hop) - 1))
    return {
        "shell": f"{shell[0]}x{shell[1]}", "n_sats": S,
        "T": len(eng.grid_t), "horizon_h": horizon_h,
        "window_steps": eng._window_steps,
        "dense_build_s": round(dense_build_s, 4),
        "csr_build_s": round(csr_build_s, 4),
        "dense_mb": round(dense_mb, 1),
        "csr_mb": round(csr_mb, 2),
        "csr_edges": int(g_csr.n_edges),
        "route_s": round(route_s, 4),
        "reachable_frac": round(float(np.isfinite(arr).mean()), 4),
        "sched_events": n,
        "sched_eps": round(n / sched_s, 2),
        "exit_hops_mean": round(float(np.mean(hops)), 2) if hops else None,
    }


def bench_async_sweep(rounds: int, horizon_h: float = 168.0) -> dict:
    """Scheduling-only fedhap_async event throughput vs fedhap rounds on
    the paper 5x8 shell (same engine, same exclusion of local SGD)."""
    from benchmarks.sim_wallclock import run_wallclock, run_wallclock_async
    cfg = SimConfig(strategy="fedhap_async", stations="two_hap",
                    num_orbits=5, sats_per_orbit=8,
                    horizon_h=horizon_h, time_step_s=30.0, **_SIM_LITE)
    eng = RoundEngine(cfg)
    a = run_wallclock_async(cfg, rounds=rounds, eng=eng)
    f = run_wallclock(cfg, rounds=rounds, compare_legacy=False, eng=eng)
    return {
        "shell": "5x8", "stations": "two_hap", "rounds": a["rounds"],
        "async_rps": round(a["async_rps"], 2),
        "fedhap_rps": round(f["engine_rps"], 2),
        "ratio": round(a["async_rps"] / f["engine_rps"], 3),
    }


def bench_routing(smoke: bool) -> dict:
    if smoke:
        build_shells = [((5, 8), 6.0), ((6, 10), 6.0)]
        ea_kw = dict(horizon_h=3.0, n_ref_sources=2)
        sweep_rounds, sweep_horizon = 20, 72.0
        stitched_shells = [((6, 10), 6.0)]
        stitched_rounds = 10
        mega_shells = [((8, 12), 2.0)]
        mega_events = 6
    else:
        build_shells = [((5, 8), 12.0), ((10, 20), 6.0), ((20, 40), 2.0)]
        ea_kw = dict(horizon_h=6.0, n_ref_sources=4)
        sweep_rounds, sweep_horizon = 100, 168.0
        stitched_shells = [((10, 20), 6.0), ((20, 40), 2.0)]
        stitched_rounds = 20
        mega_shells = [((72, 22), 2.0)]
        mega_events = 30

    doc: dict = {"table_build": []}
    for shell, horizon_h in build_shells:
        row = bench_routing_build(shell, horizon_h, 60.0)
        doc["table_build"].append(row)
        print(f"routing.build[{row['shell']} x {row['T']}t]: "
              f"{row['build_s']:.3f}s ({row['table_mb']:.0f} MB)",
              flush=True)
    doc["earliest_arrival"] = bench_earliest_arrival(**ea_kw)
    r = doc["earliest_arrival"]
    print(f"routing.earliest_arrival[{r['shell']}]: batched "
          f"{r['batched_s']:.4f}s vs per-edge {r['reference_s']:.2f}s "
          f"({r['speedup']:.0f}x, allclose)", flush=True)
    doc["async_sweep"] = bench_async_sweep(sweep_rounds, sweep_horizon)
    r = doc["async_sweep"]
    print(f"routing.async_sweep[5x8]: fedhap_async {r['async_rps']:.1f} "
          f"events/s vs fedhap {r['fedhap_rps']:.1f} rounds/s "
          f"(ratio {r['ratio']:.2f})", flush=True)
    doc["stitched_sweep"] = []
    for shell, horizon_h in stitched_shells:
        row = bench_stitched_sweep(shell, horizon_h, 60.0,
                                   rounds=stitched_rounds)
        doc["stitched_sweep"].append(row)
        print(f"routing.stitched_sweep[{row['shell']} x {row['windows']}w]:"
              f" oracle build {row['oracle_build_s']:.2f}s vs stitched "
              f"cold {row['stitched_cold_s']:.2f}s / warm "
              f"{row['stitched_warm_s']:.3f}s (allclose), buffered "
              f"{row['sched_rps']:.1f} events/s", flush=True)
    doc["mega_sweep"] = []
    for shell, horizon_h in mega_shells:
        # The stitched engines just above are reference cycles (router
        # builder closures point back at the engine), so their GB-scale
        # window/delay tables survive scope exit until the cycle
        # collector runs — reclaim them before timing Starlink scale.
        gc.collect()
        row = bench_mega_sweep(shell, horizon_h, 60.0, events=mega_events)
        doc["mega_sweep"].append(row)
        print(f"routing.mega_sweep[{row['shell']}]: dense window "
              f"{row['dense_build_s']:.2f}s ({row['dense_mb']:.0f} MB) vs "
              f"CSR {row['csr_build_s']:.2f}s ({row['csr_mb']:.1f} MB, "
              f"{row['csr_edges']} edges), route {row['route_s']:.3f}s, "
              f"buffered {row['sched_eps']:.1f} events/s", flush=True)
    return doc


def bench_sim_fused(smoke: bool) -> list[dict]:
    """Fused plan-ahead blocks vs the per-round/per-event reference for
    the FedHAP family (local SGD excluded, as in ``sim_wallclock``)."""
    from benchmarks.sim_wallclock import (
        run_wallclock_cycles,
        run_wallclock_fused,
    )
    if smoke:
        shells = [((5, 8), 20, 20)]
    else:
        shells = [((5, 8), 100, 100), ((10, 20), 100, 40)]
    out = []
    for shell, rounds, cycle_rounds in shells:
        # Long horizon: fedhap rounds take hours of sim time each.
        cfg = SimConfig(strategy="fedhap", stations="two_hap",
                        num_orbits=shell[0], sats_per_orbit=shell[1],
                        horizon_h=600.0, time_step_s=60.0, **_SIM_LITE)
        eng = RoundEngine(cfg)
        rows = [("fedhap", run_wallclock_fused(
            cfg, rounds=rounds, eng=eng), "per_round_rps")]
        for strat in ("fedhap_async", "fedhap_buffered"):
            rows.append((strat, run_wallclock_cycles(
                cfg, rounds=cycle_rounds, eng=eng, strategy=strat),
                "per_event_rps"))
        for strat, res, ref_key in rows:
            row = {
                "strategy": strat, "shell": f"{shell[0]}x{shell[1]}",
                "stations": "two_hap", "rounds": res["rounds"],
                "per_round_rps": round(res[ref_key], 2),
                "fused_rps": round(res["fused_rps"], 2),
                "speedup": round(res["speedup"], 2),
            }
            out.append(row)
            print(f"  sim_fused[{strat} x {row['shell']}]: fused "
                  f"{row['fused_rps']:.1f} vs per-round "
                  f"{row['per_round_rps']:.1f} rounds/s "
                  f"({row['speedup']:.2f}x)", flush=True)
    return out


def _sharded_sample(sc: dict, data_shards: int) -> dict:
    """Fused fedhap rounds/s for one scenario at one shard count in
    this process: the first ``run()`` pays compilation, the second
    measures steady-state throughput (real local SGD included —
    sharding accelerates the train+fold megastep itself, unlike the
    scheduling-only sections)."""
    cfg = SimConfig(strategy="fedhap", stations=sc["stations"],
                    num_orbits=sc.get("num_orbits", 5),
                    sats_per_orbit=sc.get("sats_per_orbit", 8),
                    shells=sc.get("shells", ""),
                    data_shards=data_shards,
                    local_steps=sc["local_steps"],
                    horizon_h=sc["horizon_h"], time_step_s=60.0,
                    max_rounds=sc["rounds"], target_accuracy=2.0,
                    **_SIM_LITE)
    eng = RoundEngine(cfg)
    t0 = time.perf_counter()
    eng.run()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = eng.run()
    dt = time.perf_counter() - t0
    assert res.rounds == sc["rounds"], \
        f"horizon exhausted: {res.rounds}/{sc['rounds']} rounds"
    return {"rounds": res.rounds, "compile_s": round(compile_s, 2),
            "rps": round(res.rounds / dt, 3)}


def bench_sim_sharded(smoke: bool) -> list[dict]:
    """1-vs-D scaling of the sharded fused megastep
    (``SimConfig.data_shards`` -> shard_map over the satellite axis):
    fedhap on a dense gateway grid, single-shell and two-shell. D is
    this process's device count, and one process drives every device:
    CI's multidevice tier forces 8 host devices through ``XLA_FLAGS``,
    a TPU host brings its chips. On one physical CPU the forced
    devices share cores, so ``scaling`` measures dispatch/collective
    overhead rather than true speedup. Skipped with one device."""
    import jax

    devices = jax.device_count()
    if devices < 2:
        print("  sim_sharded: skipped (one device)", flush=True)
        return []
    if smoke:
        scenarios = [
            dict(stations="grid:3x6", num_orbits=6, sats_per_orbit=10,
                 horizon_h=12.0, rounds=3, local_steps=2),
            dict(stations="grid:3x6",
                 shells="shells:3x10@550+3x10@1200/60",
                 horizon_h=12.0, rounds=3, local_steps=2),
        ]
    else:
        scenarios = [
            dict(stations="grid:3x6", num_orbits=20, sats_per_orbit=40,
                 horizon_h=24.0, rounds=6, local_steps=2),
            dict(stations="grid:3x6",
                 shells="shells:12x40@550+8x40@1200/60",
                 horizon_h=24.0, rounds=6, local_steps=2),
        ]
    out = []
    for sc in scenarios:
        label = sc.get("shells") or \
            f"{sc['num_orbits']}x{sc['sats_per_orbit']}"
        row: dict = {"scenario": f"{sc['stations']} x {label}",
                     "devices": devices}
        for tag, shards in (("1", 0), ("sharded", devices)):
            res = _sharded_sample(sc, shards)
            row[f"rps_{tag}"] = res["rps"]
            row[f"compile_s_{tag}"] = res["compile_s"]
            row["rounds"] = res["rounds"]
        row["scaling"] = round(row["rps_sharded"] / row["rps_1"], 3)
        out.append(row)
        print(f"  sim_sharded[{row['scenario']}]: "
              f"{row['rps_sharded']:.2f} rounds/s on {devices} devices "
              f"vs {row['rps_1']:.2f} on 1 "
              f"(scaling {row['scaling']:.2f}x)", flush=True)
    return out


def bench_sweep(scenarios, horizon_h: float, step_s: float,
                rounds: int = 10) -> list[dict]:
    """Mega-constellation sweep: grid build + scheduler rounds/sec."""
    from benchmarks.sim_wallclock import run_wallclock
    out = []
    for stations, shell in scenarios:
        cfg = _scenario_cfg(stations, shell, horizon_h, step_s)
        grid = bench_grid_build(stations, shell, horizon_h, step_s,
                                check=False)
        t0 = time.perf_counter()
        res = run_wallclock(cfg, rounds=rounds, compare_legacy=False)
        row = {
            "stations": stations, "shell": f"{shell[0]}x{shell[1]}",
            "n_stations": grid["n_stations"], "n_sats": grid["n_sats"],
            "T": grid["T"],
            "grid_build_s": grid["batched_s"],
            "rounds": res["rounds"],
            "rounds_per_sec": round(res["engine_rps"], 2),
            "wall_s": round(time.perf_counter() - t0, 2),
        }
        out.append(row)
        print(f"  sweep[{stations} x {row['shell']}]: "
              f"grid {row['grid_build_s']:.3f}s, "
              f"{row['rounds_per_sec']:.1f} rounds/s", flush=True)
    return out


def _plan_drive(eng, rounds: int) -> tuple[int, float]:
    """Plan-phase throughput: plan_round + plane resolve per round,
    no SGD — the host-side work the client plane adds to a round."""
    from repro.sim.strategies import get_strategy
    strat = get_strategy("fedhap")()
    all_sats = list(range(eng.n_sats))
    t, done = 0.0, 0
    t0 = time.perf_counter()
    for _ in range(rounds):
        plan = strat.plan_round(eng, t)
        if plan is None:
            break
        eng.sample_indices(all_sats, t)
        t = plan.t_next
        done += 1
    return done, time.perf_counter() - t0


def bench_client_plane(smoke: bool) -> list[dict]:
    """Static vs virtual-client-plane planning overhead.

    Drives the fedhap plan phase (scheduling + per-round sample-index
    resolution, no local SGD) on one engine per plane and reports
    rounds/s. The geo plane must stay above 0.5x the static plane's
    planning throughput — the acceptance bar for streaming acquisition
    at >= 10k virtual clients.
    """
    if smoke:
        shell, horizon_h, rounds = (10, 20), 24.0, 4
        planes = ["sampled:0.1x10000", "geo:32x10000@0.1"]
    else:
        shell, horizon_h, rounds = (20, 40), 48.0, 8
        planes = ["sampled:0.1x10000", "geo:64x10000@0.1"]
    lite = dict(_SIM_LITE, num_samples=20_000)  # >= 1 sample / client

    def make(plane_spec: str) -> tuple[RoundEngine, float]:
        cfg = SimConfig(strategy="fedhap", stations="two_hap",
                        num_orbits=shell[0], sats_per_orbit=shell[1],
                        horizon_h=horizon_h, time_step_s=60.0,
                        clients=plane_spec, **lite)
        t0 = time.perf_counter()
        eng = RoundEngine(cfg)
        return eng, time.perf_counter() - t0

    out = []
    eng, init_s = make("static")
    done, wall = _plan_drive(eng, rounds)
    static_rps = done / wall
    out.append({
        "shell": f"{shell[0]}x{shell[1]}", "stations": "two_hap",
        "plane": "static", "n_clients": eng.n_sats, "rounds": done,
        "engine_init_s": round(init_s, 2),
        "plan_rps": round(static_rps, 2),
    })
    print(f"  client_plane[static x {out[0]['shell']}]: "
          f"{static_rps:.2f} plan rounds/s", flush=True)
    for spec in planes:
        eng, init_s = make(spec)
        done, wall = _plan_drive(eng, rounds)
        rps = done / wall
        desc = eng.client_plane.describe()
        row = {
            "shell": f"{shell[0]}x{shell[1]}", "stations": "two_hap",
            "plane": spec, "n_clients": desc["clients"],
            "rounds": done,
            "engine_init_s": round(init_s, 2),
            "plan_rps": round(rps, 2),
            "vs_static": round(rps / static_rps, 3),
        }
        if "regions" in desc:
            row["regions"] = desc["regions"]
            assert rps > 0.5 * static_rps, (
                f"geo plane planning throughput {rps:.2f} rounds/s fell "
                f"below 0.5x static ({static_rps:.2f})")
        out.append(row)
        print(f"  client_plane[{spec} x {row['shell']}]: "
              f"{rps:.2f} plan rounds/s ({row['vs_static']:.2f}x static)",
              flush=True)
    return out


_FAULTS_SPEC = ("sat_outage=0.05,isl_drop=0.1,upload_loss=0.15,"
                "hap_outage=0.05,mtbf_h=2,mttr_h=1")


def bench_faults(smoke: bool) -> dict:
    """Fault-plane cost: scheduling overhead + accuracy vs outage rate.

    Overhead: the fedhap plan phase on a clean vs a faulty engine of
    the same shell — the fault plane's per-round cost is pure plan-side
    (masked tables, retry pricing), so plan rounds/s is the metric.
    The faulty plane must stay above 0.5x the clean plan throughput
    (guarded as ``faults.overhead.vs_clean`` by check_regression).

    Sweep: final accuracy of a small fedhap sim across outage rates —
    diagnostic trend data (graceful degradation), not a guarded rate.
    """
    shell = (6, 10) if smoke else (10, 20)
    horizon_h, rounds = (12.0, 4) if smoke else (24.0, 8)

    def make(faults: str) -> tuple[RoundEngine, float]:
        cfg = SimConfig(strategy="fedhap", stations="two_hap",
                        num_orbits=shell[0], sats_per_orbit=shell[1],
                        horizon_h=horizon_h, time_step_s=60.0,
                        faults=faults, **_SIM_LITE)
        t0 = time.perf_counter()
        eng = RoundEngine(cfg)
        return eng, time.perf_counter() - t0

    eng, clean_init = make("")
    done_c, wall_c = _plan_drive(eng, rounds)
    clean_rps = done_c / wall_c
    eng, faulty_init = make(_FAULTS_SPEC)
    done_f, wall_f = _plan_drive(eng, rounds)
    faulty_rps = done_f / wall_f
    overhead = {
        "shell": f"{shell[0]}x{shell[1]}", "stations": "two_hap",
        "spec": _FAULTS_SPEC,
        "clean_init_s": round(clean_init, 2),
        "faulty_init_s": round(faulty_init, 2),
        "clean_plan_rps": round(clean_rps, 2),
        "faulty_plan_rps": round(faulty_rps, 2),
        "vs_clean": round(faulty_rps / clean_rps, 3),
    }
    print(f"  faults[overhead x {overhead['shell']}]: "
          f"{faulty_rps:.2f} faulty vs {clean_rps:.2f} clean plan "
          f"rounds/s ({overhead['vs_clean']:.2f}x)", flush=True)

    sweep = []
    for rate in (0.0, 0.05, 0.2):
        spec = "" if rate == 0.0 else (
            f"sat_outage={rate},upload_loss={rate},"
            f"hap_outage={rate},mtbf_h=2,mttr_h=1")
        cfg = SimConfig(strategy="fedhap", stations="two_hap",
                        num_orbits=5, sats_per_orbit=8,
                        horizon_h=24.0, time_step_s=60.0,
                        max_rounds=3 if smoke else 6,
                        local_steps=2, faults=spec, **_SIM_LITE)
        res = RoundEngine(cfg).run(fused=True)
        sweep.append({"outage_rate": rate, "rounds": res.rounds,
                      "final_acc": round(res.final_accuracy, 4)})
        print(f"  faults[sweep rate={rate}]: {res.rounds} rounds, "
              f"acc {res.final_accuracy:.4f}", flush=True)
    return {"overhead": overhead, "accuracy_sweep": sweep}


def run(smoke: bool = False, sim_wallclock: bool = False,
        rounds: int = 25) -> dict:
    doc: dict = {"schema": 1, "smoke": smoke}

    if smoke:
        grid_scenarios = [("two_hap", (5, 8))]
        sweep_scenarios = [("haps:4", (6, 10)), ("grid:3x6", (6, 10))]
        horizon_h, step_s, sweep_rounds = 6.0, 60.0, 5
    else:
        grid_scenarios = [("two_hap", (5, 8)), ("two_hap", (20, 40)),
                          ("grid:3x6", (20, 40))]
        sweep_scenarios = [("haps:4", (10, 20)), ("grid:3x6", (10, 20)),
                           ("haps:8", (20, 40)), ("grid:6x12", (20, 40))]
        horizon_h, step_s, sweep_rounds = 12.0, 60.0, 10

    doc["grid_build"] = []
    for stations, shell in grid_scenarios:
        row = bench_grid_build(stations, shell, horizon_h, step_s)
        doc["grid_build"].append(row)
        print(f"grid_build[{stations} x {row['shell']}]: "
              f"batched {row['batched_s']:.3f}s vs per-pair "
              f"{row['pairwise_s']:.3f}s ({row['speedup']:.1f}x)",
              flush=True)

    dt_shell = (5, 8) if smoke else (10, 20)
    doc["delay_table"] = [bench_delay_table(
        "two_hap", dt_shell, horizon_h, step_s,
        n_queries=200 if smoke else 2000)]
    r = doc["delay_table"][0]
    print(f"delay_table[two_hap x {r['shell']}]: lookup {r['lookup_us']}us "
          f"gather {r['gather_us']}us vs reference {r['reference_us']}us "
          f"({r['speedup']:.0f}x)", flush=True)

    doc["routing"] = bench_routing(smoke)
    # The routing tier holds multi-hundred-MB window/delay tables alive
    # until its engines die; reclaim them so the later sections measure
    # steady-state throughput, not allocator pressure.
    gc.collect()

    doc["sim_fused"] = bench_sim_fused(smoke)
    gc.collect()

    doc["sim_sharded"] = bench_sim_sharded(smoke)
    gc.collect()

    doc["sweep"] = bench_sweep(sweep_scenarios, horizon_h, step_s,
                               rounds=sweep_rounds)
    gc.collect()

    print("client_plane:", flush=True)
    doc["client_plane"] = bench_client_plane(smoke)
    gc.collect()

    print("faults:", flush=True)
    doc["faults"] = bench_faults(smoke)

    if sim_wallclock:
        from benchmarks.sim_wallclock import report
        cfg = SimConfig(strategy="fedhap", stations="two_hap",
                        model_kind="mlp", num_samples=4000,
                        eval_samples=500, horizon_h=72.0, time_step_s=30.0)
        doc["sim_wallclock"] = report("geometry", cfg, rounds=rounds)
    else:
        doc["sim_wallclock"] = None
    return doc


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small scenarios (CI tier)")
    ap.add_argument("--sim-wallclock", action="store_true",
                    help="also run the paper-5x8 engine-vs-legacy "
                         "rounds/sec comparison")
    ap.add_argument("--rounds", type=int, default=25)
    ap.add_argument("--sharded-only", action="store_true",
                    help="run only the sim_sharded 1-vs-D device "
                         "scaling section (the CI multi-device tier)")
    ap.add_argument("--faults-only", action="store_true",
                    help="run only the fault-plane overhead + "
                         "accuracy-vs-outage section (the CI chaos "
                         "tier)")
    ap.add_argument("--out", default=str(DEFAULT_OUT),
                    help="where to write BENCH_sim.json")
    args = ap.parse_args()
    use_compile_cache()
    if args.sharded_only:
        doc = {"schema": 1, "smoke": args.smoke,
               "sim_sharded": bench_sim_sharded(args.smoke)}
    elif args.faults_only:
        print("faults:", flush=True)
        doc = {"schema": 1, "smoke": args.smoke,
               "faults": bench_faults(args.smoke)}
    else:
        doc = run(smoke=args.smoke, sim_wallclock=args.sim_wallclock,
                  rounds=args.rounds)
    out = pathlib.Path(args.out)
    out.write_text(json.dumps(doc, indent=2) + "\n")
    print(f"wrote {out}", flush=True)


if __name__ == "__main__":
    main()
