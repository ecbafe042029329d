"""Paper Fig. 3 panels: accuracy-vs-time curves across settings.

  a: FedHAP vs SOTA (covered by bench_table2 histories)
  b: IID, CNN/MLP x GS/oneHAP
  c: non-IID, CNN/MLP x GS/oneHAP
  d: two HAPs, IID + non-IID

Quick tier shrinks dataset/rounds for CPU; --full reproduces the paper
scale. Emits JSON histories per curve.
"""
from __future__ import annotations

import argparse
import dataclasses
import json

from repro.sim import SatcomSimulator, SimConfig
from repro.launch.compile_cache import use_compile_cache


def _curves(panel: str, quick: bool) -> dict[str, SimConfig]:
    base = dict(strategy="fedhap")
    if quick:
        base.update(num_samples=6000, eval_samples=1200, local_steps=12,
                    max_rounds=6, horizon_h=72.0, time_step_s=60.0,
                    num_orbits=3, sats_per_orbit=4)
    else:
        base.update(num_samples=70000, eval_samples=6000, local_steps=54,
                    max_rounds=120, horizon_h=72.0)
    mk = lambda **kw: SimConfig(**{**base, **kw})
    if panel == "b":
        return {
            "CNN-oneHAP-iid": mk(model_kind="cnn", stations="one_hap",
                                 iid=True),
            "MLP-oneHAP-iid": mk(model_kind="mlp", stations="one_hap",
                                 iid=True),
            "CNN-GS-iid": mk(model_kind="cnn", stations="gs", iid=True),
            "MLP-GS-iid": mk(model_kind="mlp", stations="gs", iid=True),
        }
    if panel == "c":
        return {
            "CNN-oneHAP-noniid": mk(model_kind="cnn", stations="one_hap"),
            "MLP-oneHAP-noniid": mk(model_kind="mlp", stations="one_hap"),
            "CNN-GS-noniid": mk(model_kind="cnn", stations="gs"),
            "MLP-GS-noniid": mk(model_kind="mlp", stations="gs"),
        }
    if panel == "d":
        # quick tier uses the MLP (XLA's CPU conv path is ~50x off the
        # roofline on this host); --full restores the paper's CNN.
        kind = "mlp" if quick else "cnn"
        return {
            f"{kind.upper()}-twoHAP-iid": mk(model_kind=kind,
                                             stations="two_hap", iid=True),
            f"{kind.upper()}-twoHAP-noniid": mk(model_kind=kind,
                                                stations="two_hap"),
            "MLP-oneHAP-iid": mk(model_kind="mlp", stations="one_hap",
                                 iid=True),
            "MLP-oneHAP-noniid": mk(model_kind="mlp", stations="one_hap"),
        }
    raise ValueError(panel)


def run(panel: str, quick: bool = True) -> dict:
    out = {}
    for name, cfg in _curves(panel, quick).items():
        res = SatcomSimulator(cfg).run()
        out[name] = {
            "final_acc": round(res.final_accuracy, 4),
            "history": [(round(t, 2), round(a, 4))
                        for t, _, a in res.history],
        }
        print(f"  {name}: acc={out[name]['final_acc']} "
              f"({len(out[name]['history'])} pts)", flush=True)
    return out


def sim_wallclock(quick: bool = True, rounds: int = 25) -> dict:
    """Simulator rounds/sec for this bench's constellation tier (quick:
    3x4, full: the paper's 5x8) — engine vs seed-style scans."""
    from benchmarks.sim_wallclock import report
    cfg = next(iter(_curves("d", quick).values()))
    cfg = dataclasses.replace(cfg, strategy="fedhap",
                              num_samples=4000, eval_samples=500)
    return report("fig3", cfg, rounds=rounds)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--panel", default="c", choices=["b", "c", "d"])
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--sim-wallclock", action="store_true",
                    help="report simulator rounds/sec vs the seed-style "
                         "implementation instead of running the panel")
    ap.add_argument("--rounds", type=int, default=25)
    ap.add_argument("--out")
    args = ap.parse_args()
    use_compile_cache()
    if args.sim_wallclock:
        res = sim_wallclock(quick=not args.full, rounds=args.rounds)
        if args.out:
            json.dump(res, open(args.out, "w"), indent=1)
        raise SystemExit(0)
    res = run(args.panel, quick=not args.full)
    if args.out:
        json.dump(res, open(args.out, "w"), indent=1)
