"""Shared pieces of the benchmark's CPU tests.

The tests run on the CPU (``JAX_PLATFORMS=cpu``) at sizes a test run
holds: ``make_checkout`` copies the benchmark into a temporary checkout,
where ``add_cell`` adds a cell as data alone, as a later change would.
"""
from __future__ import annotations

import json
import math
import os
import pathlib
import shutil
import sys
import tempfile

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The tests compile for the CPU; keep their compile cache out of the
# checkout's.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="bench-tests-cache-"))
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

# A 2x2 shell over the paper's two HAPs, one local step, a small data
# set: four replicas, small enough for the CPU.
TINY_SIM = {"num_orbits": 2, "sats_per_orbit": 2, "num_samples": 2000,
            "eval_samples": 100, "local_steps": 1}

# The paper's 2NN (``payloads/mlp.py``, the program's ``model_kind="mlp"``)
# at its published widths, as a configuration's model group.
MLP_MODEL = {"kind": "mlp", "input_dim": 784, "hidden": [200, 200],
             "num_classes": 10, "params": 199_210, "dtype": "float32",
             "matmul_precision": "default",
             "init_scale": {"w0": 1 / math.sqrt(784),
                            "w1": 1 / math.sqrt(200),
                            "w2": 1 / math.sqrt(200)}}


def load(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def dump(path: pathlib.Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=2) + "\n")


def make_checkout(tmp_path: pathlib.Path) -> pathlib.Path:
    """A copy of ``BENCHMARK.json`` and ``bench/``; returns its bench
    directory."""
    ck = tmp_path / "checkout"
    shutil.copytree(BENCH, ck / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", ck / "BENCHMARK.json")
    return ck / "bench"


def add_cell(bench_dir: pathlib.Path, name: str, config: dict,
             traffic: dict, limits: dict, chips: int = 1,
             metrics: tuple = ()) -> None:
    """Add configuration, traffic, limits and per-layer metric entries
    as files and entries only."""
    cfg_name, mix = name.split(".", 1)
    dump(bench_dir / "configs" / f"{cfg_name}.json", config)
    dump(bench_dir / "traffic" / f"{mix}.json", traffic)
    dump(bench_dir / "limits" / f"{name}.json", limits)
    spec_path = bench_dir.parent / "BENCHMARK.json"
    spec = load(spec_path)
    spec["configs"].append({"name": cfg_name, "source": "test",
                            "file": f"bench/configs/{cfg_name}.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": name, "config": cfg_name,
                              "traffic": mix, "chips": chips,
                              "why": "test"})
    spec["per_layer"] += list(metrics)
    dump(spec_path, spec)


def tiny_cell(bench_dir: pathlib.Path, name: str, like: str,
              chips: int = 1, model: dict | None = None, **sim) -> None:
    """A CPU-sized copy of cell ``like``: its traffic with a 3-update
    episode and a 6 h horizon, its limits, the 2x2 shell; ``model``, if
    given, in place of its configuration's model group."""
    spec = load(bench_dir.parent / "BENCHMARK.json")
    w = {c["name"]: c for c in spec["workloads"]}[like]
    cfg_file = {c["name"]: c for c in spec["configs"]}[w["config"]]["file"]
    config = load(bench_dir.parent / cfg_file)
    config["sim"].update(TINY_SIM, **sim)
    if model is not None:
        config["model"] = model
    config["chips"] = chips
    traffic = load(bench_dir / "traffic" / f"{w['traffic']}.json")
    traffic["sim"]["horizon_h"] = 6.0
    traffic["episode"]["max_rounds"] = 3
    if traffic["family"] == "tick_fedsat":
        traffic["probe"] = {"episodes": [3], "calls": 1}
    add_cell(bench_dir, name, config, traffic,
             load(bench_dir / "limits" / f"{like}.json"), chips)
