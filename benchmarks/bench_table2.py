"""Paper Table II: accuracy + convergence time per FL-Satcom method
(non-IID, CNN in the paper; the quick tier uses MLP for CPU tractability
— pass --full for the CNN/70k configuration).

Emits CSV rows: method,final_acc,hours_to_80pct,rounds,sim_hours.
"""
from __future__ import annotations

import argparse
import json
import time

from repro.core.strategies import TABLE2_SETUPS
from repro.launch.compile_cache import use_compile_cache
from repro.sim import SatcomSimulator, SimConfig
import dataclasses


def run(quick: bool = True, target: float = 0.80,
        methods: list[str] | None = None) -> list[dict]:
    rows = []
    for name, base in TABLE2_SETUPS.items():
        if methods and name not in methods:
            continue
        if quick:
            is_async = base.strategy in ("fedsat", "fedspace")
            cfg = dataclasses.replace(
                base, model_kind="mlp", num_samples=8000, eval_samples=1500,
                local_steps=40, max_rounds=60 if is_async else 12,
                horizon_h=72.0, time_step_s=60.0, iid=False)
        else:
            cfg = dataclasses.replace(
                base, model_kind="cnn", num_samples=70000,
                eval_samples=6000, local_steps=54, max_rounds=120,
                horizon_h=72.0, iid=False)
        t0 = time.perf_counter()
        res = SatcomSimulator(cfg).run()
        tta = res.time_to_accuracy(target)
        rows.append({
            "method": name,
            "final_acc": round(res.final_accuracy, 4),
            f"hours_to_{int(target*100)}pct":
                round(tta, 2) if tta else None,
            "rounds": res.rounds,
            "sim_hours": round(res.sim_hours, 2),
            "wall_s": round(time.perf_counter() - t0, 1),
            "history": [(round(t, 2), round(a, 4))
                        for t, _, a in res.history],
        })
        print(f"  {name}: acc={rows[-1]['final_acc']} "
              f"rounds={rows[-1]['rounds']} "
              f"sim_h={rows[-1]['sim_hours']}", flush=True)
    return rows


def main(quick: bool = True) -> None:
    rows = run(quick=quick)
    print("method,final_acc,rounds,sim_hours")
    for r in rows:
        print(f"{r['method']},{r['final_acc']},{r['rounds']},"
              f"{r['sim_hours']}")


def sim_wallclock(rounds: int = 25) -> dict:
    """Simulator rounds/sec on the paper's 5x8 constellation (no SGD):
    vectorized engine vs a faithful port of the seed's per-round scans."""
    from benchmarks.sim_wallclock import report
    cfg = SimConfig(strategy="fedhap", stations="two_hap",
                    model_kind="mlp", num_samples=4000, eval_samples=500,
                    horizon_h=72.0, time_step_s=30.0)
    return report("table2", cfg, rounds=rounds)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--sim-wallclock", action="store_true",
                    help="report simulator rounds/sec vs the seed-style "
                         "implementation instead of running Table II")
    ap.add_argument("--rounds", type=int, default=25)
    ap.add_argument("--out")
    args = ap.parse_args()
    use_compile_cache()
    if args.sim_wallclock:
        res = sim_wallclock(rounds=args.rounds)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(res, f, indent=1)
        raise SystemExit(0)
    rows = run(quick=not args.full)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
