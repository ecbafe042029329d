"""The model and the data of a configuration, found by name.

- ``bench/payloads/<model.kind>.py``: the model the satellites train,
  in plain ``jax.numpy``;
- ``bench/datasets/<sim.dataset>.py``: the data, its eval/train split
  and how the configuration partitions it over the satellites.

The plain reference is a payload and a dataset together; its docstring
(``reference.py``) lists what each file has to give.
"""
from __future__ import annotations

import functools
import importlib.util
import pathlib
import types

BENCH = pathlib.Path(__file__).resolve().parent


def load_module(path: pathlib.Path) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_")
        .replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.cache
def _part(bench_dir: pathlib.Path, kind: str, name: str) -> types.ModuleType:
    path = bench_dir / kind / f"{name}.py"
    if not path.is_file():
        have = sorted(p.stem for p in (bench_dir / kind).glob("*.py"))
        raise SystemExit(f"unknown {kind[:-1]} {name!r}: the benchmark has "
                         f"{kind} {have} in {bench_dir / kind}")
    return load_module(path)


def payload(kind: str, bench_dir: pathlib.Path = BENCH) -> types.ModuleType:
    """``payloads/<kind>.py``; exits naming the payloads there are."""
    return _part(bench_dir, "payloads", kind)


def dataset(name: str, bench_dir: pathlib.Path = BENCH) -> types.ModuleType:
    """``datasets/<name>.py``; exits naming the datasets there are."""
    return _part(bench_dir, "datasets", name)
