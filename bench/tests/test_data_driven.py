"""A new cell comes in as data: files and entries, no harness edit."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys
import types

import pytest

import run
from conftest import (BENCH, MLP_MODEL, ROOT, add_cell, load, make_checkout,
                      tiny_cell)

METRIC = '''"""A metric of a later change: the engine build in milliseconds."""


def read(ctx):
    return 1e3 * ctx.engine_build_s
'''


def test_new_cell_is_listed_and_loaded_from_its_files(tmp_path):
    bench = make_checkout(tmp_path)
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    config = load(bench / "configs" / "paper-5x8.json")
    config["name"] = "wide-10x20"
    config["sim"].update(num_orbits=10, sats_per_orbit=20)
    traffic = load(bench / "traffic" / "fedhap-16r.json")
    traffic["episode"]["max_rounds"] = 24
    (bench / "metrics" / "build_ms.py").write_text(METRIC)
    metric = {"name": "build_ms", "unit": "ms", "better": "lower",
              "source": "host_clock", "layer": "engine set-up",
              "moves": "setup_s", "workloads": ["wide-10x20.fedhap-24r"]}
    add_cell(bench, "wide-10x20.fedhap-24r", config, traffic,
             {"init_gap": 0.0}, metrics=(metric,))
    # Every file that was there is unchanged.
    assert all(p.read_bytes() == b for p, b in before.items())

    assert "wide-10x20.fedhap-24r" in run.cell_names(bench)
    cell = run.load_cell(bench, "wide-10x20.fedhap-24r")
    assert cell.config["sim"]["num_orbits"] == 10
    assert cell.traffic["episode"]["max_rounds"] == 24
    assert cell.family.METHOD == "run_block"
    assert cell.limits == {"init_gap": 0.0}
    assert [m["name"] for m in cell.end_to_end] == ["updates_per_s",
                                                    "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert "build_ms" in names and "fold_roofline" not in names
    ctx = types.SimpleNamespace(engine_build_s=2.5, compile_s=1.0)
    cell.per_layer = [m for m in cell.per_layer
                      if m["name"] in ("build_ms", "compile_s")]
    assert run.per_layer_metrics(cell, ctx) == {
        "compile_s": {"value": 1.0, "unit": "s"},
        "build_ms": {"value": 2500.0, "unit": "ms"}}
    # The cells that were there do not see the new metric.
    old = run.load_cell(bench, "paper-5x8.fedhap")
    assert "build_ms" not in [m["name"] for m in old.per_layer]


def _run(cwd: pathlib.Path, env_extra: dict,
         workload: str = "paper-5x8.fedhap") -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload,
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


# Drives a run of a cell in the checkout it is started in, with the
# checkout's own harness, skipping only the look for a chip.
ON_CPU = """
import json, sys
sys.path[:0] = ["bench", sys.argv[2]]
import jax
import run
cell = run.load_cell(run.BENCH, sys.argv[1])
res = run.run_cell(cell, 2**31 + 5, 0.01, False, jax.devices()[:1])
print(json.dumps({"correct": res["correct"], "checks": res["checks"],
                  "payload": cell.payload.__file__}))
"""


def test_new_payload_is_found_by_name_and_run(tmp_path):
    """A model that only the checkout has: its payload file and its
    configuration are new files, and the checkout's harness loads the
    payload by the configuration's ``model.kind``, in the runner, the
    reference and the counts, and runs the cell correct."""
    bench = make_checkout(tmp_path)
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "payloads" / "two_nn.py").write_text(
        (BENCH / "payloads" / "mlp.py").read_text())
    tiny_cell(bench, "tiny-n.fedhap", "paper-5x8.fedhap",
              model={**MLP_MODEL, "kind": "two_nn"}, model_kind="mlp")
    assert all(p.read_bytes() == b for p, b in before.items())
    assert not (BENCH / "payloads" / "two_nn.py").exists()
    proc = subprocess.run(
        [sys.executable, "-c", ON_CPU, "tiny-n.fedhap", str(ROOT / "src")],
        cwd=bench.parent, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert pathlib.Path(res["payload"]) == bench / "payloads" / "two_nn.py"


@pytest.mark.parametrize("part,have", [("payload", "'cnn', 'mlp'"),
                                       ("dataset", "'digits'")])
def test_unknown_payload_or_dataset_exits_naming_those_there_are(
        tmp_path, part, have):
    bench = make_checkout(tmp_path)
    config = load(bench / "configs" / "paper-5x8.json")
    if part == "payload":
        config["model"]["kind"] = "nosuch"
    else:
        config["sim"]["dataset"] = "nosuch"
    add_cell(bench, "odd-5x8.fedhap", config,
             load(bench / "traffic" / "fedhap-16r.json"),
             load(bench / "limits" / "paper-5x8.fedhap.json"))
    proc = _run(bench.parent, {}, "odd-5x8.fedhap")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert f"unknown {part} 'nosuch'" in proc.stderr
    assert have in proc.stderr
    assert "no TPU" not in proc.stderr


def test_no_tpu_exits_nonzero_and_prints_nothing():
    proc = _run(ROOT, {})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    ck = tmp_path / "alone"
    shutil.copytree(ROOT / "bench", ck / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", ck)
    proc = _run(ck, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_every_cell_of_the_benchmark_loads():
    spec = load(ROOT / "BENCHMARK.json")
    for name in run.cell_names(ROOT / "bench"):
        cell = run.load_cell(ROOT / "bench", name)
        assert cell.chips in (1, 4)
        assert set(cell.limits) == set(run.CHECKS)
        assert cell.config["name"] in {c["name"] for c in spec["configs"]}
        for m in cell.per_layer:
            assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
