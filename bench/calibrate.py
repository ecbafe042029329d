#!/usr/bin/env python3
"""Readings that the limits of a cell's correctness check are set from.

    python bench/calibrate.py --workload paper-5x8.fedhap \
        --seeds 101 102 103 --modes sound control half_batch answer

For each seed, in one process, the cell's engine is built and driven as
a run drives it: the probe episodes, then one episode as the window runs
it. That is done once for the sound program and once with each fault of
``faults.FAULTS`` planted under the timed path; each time the program's
state is freed after. The plain float32 reference then replays the
sound program's records, and each mode is compared with it:

- ``sound``: the program itself (its lower readings);
- ``control``: the reference computed in bfloat16, in the program's
  place;
- a fault of ``faults.FAULTS``: the program with that fault planted.

Prints one JSON line per seed and mode, and a summary of the largest
and smallest reading of each number per mode. Needs the cell's chips;
the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import faults
import run
from reference import Reference


def program(cell, seed: int, fault) -> list:
    """The records a run compares (the probes, then one window
    episode), with ``fault`` (None: none) planted."""
    with faults.planted(fault, cell.family):
        sim = run.sim_config(cell, seed)
        eng, rec, probes, _ = run.set_up(cell, sim)
        res = rec.episode(eng, sim["max_rounds"])
        records = probes + [rec.last_episode(res)]
        rec.unwrap()
    del eng, rec, res
    run.free_device_state()
    return records


def same_feeds(a: list, b: list) -> bool:
    return all(len(x["feeds"]) == len(y["feeds"]) and all(
        fx.keys() == fy.keys() and all(np.array_equal(fx[k], fy[k])
                                       for k in fx)
        for fx, fy in zip(x["feeds"], y["feeds"])) for x, y in zip(a, b))


def readings(cell, seed: int, modes: list, devices: list) -> dict:
    planted = [m for m in modes if m in faults.FAULTS]
    got = {m: program(cell, seed, m) for m in planted}
    sound = program(cell, seed, None)
    ref = Reference(cell.config, seed, devices=devices)
    want = run.replay(cell, sound, ref)
    out = {}
    for mode in modes:
        if mode == "sound":
            recs = sound
        elif mode == "control":
            ctl = Reference(cell.config, seed, dtype="bfloat16",
                            devices=devices)
            recs = run.in_programs_place(sound, *run.replay(cell, sound, ctl))
        else:
            recs = got[mode]
            if not same_feeds(recs, sound):
                raise RuntimeError(f"{mode}: the plan changed under the "
                                   f"fault; replay it on its own")
        out[mode] = run.compare(cell, recs, *want)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--modes", nargs="+", default=["sound", "control"])
    args = ap.parse_args(argv)
    cell = run.load_cell(run.BENCH, args.workload)
    sys.path.insert(0, str(run.BENCH.parent / "src"))
    import jax
    from repro.launch.compile_cache import use_compile_cache
    devices = run.find_chips(cell.chips)
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    table = {m: [] for m in args.modes}
    for seed in args.seeds:
        t = time.perf_counter()
        for mode, vals in readings(cell, seed, args.modes, devices).items():
            table[mode].append(vals)
            print(json.dumps({"seed": seed, "mode": mode, **vals}),
                  flush=True)
        print(f"calibrate: seed {seed} took {time.perf_counter() - t!r} s",
              file=sys.stderr, flush=True)
    for mode, rows in table.items():
        print(json.dumps({"mode": mode, "max": {k: max(r[k] for r in rows)
                                                for k in rows[0]},
                          "min": {k: min(r[k] for r in rows)
                                  for k in rows[0]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
