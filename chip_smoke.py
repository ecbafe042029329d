#!/usr/bin/env python3
"""Bring-up check of the fused FedHAP simulator on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the data-sharded megastep on four

One chip: the paper's Table II scenario (fedhap over two HAPs, 5x8
Walker shell at 2000 km / 80 deg, the 1,663,370-parameter paper CNN, 54
local steps of batch 32, blocks of 8 planned rounds) runs 16 rounds
through ``RoundEngine.run``. Checks:

- every accuracy is finite and in [0, 1];
- the compiled round program holds the Pallas fold (``tpu_custom_call``);
- the Pallas fold, and the einsum fold of the per-round reference, of 40
  stacked CNN replicas match a float64 numpy fold (atol=1e-6, rtol=1e-5);
- for each seed in ``SEEDS``, the fused history matches the per-round
  reference (``fused=False``): same rounds and times, accuracies within
  ``CHIP_ACC_BOUND``.

Four chips (``--chips 4``), the sharded path only:

- the 800-satellite two-shell constellation over a 3x6 gateway grid
  with ``data_shards=4`` runs two rounds; peak HBM per chip is printed;
- the sharded fold (a Pallas fold per chip, one psum) of the same 40
  stacked replicas matches the one-chip fold and float64 (atol=1e-6,
  rtol=1e-5);
- for each seed in ``SEEDS``, paper-5x8 with ``data_shards=4`` against
  the one-device run in this process: same rounds and times, accuracies
  within ``CHIP_ACC_BOUND``;
- one planned round with ``data_shards=4`` matches the same round on
  one device trained in the per-chip shape (four dispatches of 10
  replicas, summed): params within atol=1e-6, rtol=1e-5. This holds the
  sharding of indices and weights, the per-chip fold and the psum to the
  fold bound; what it leaves out, the shape of the train burst, is what
  the history bound allows for.

Times are on the host clock and end in a device sync. Every phase runs;
a failed check or phase makes the exit code 1 and suppresses the result
line. With no TPU the script exits 1 before running anything. The last
line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs.paper_cnn import CONFIG as CNN_CONFIG  # noqa: E402
from repro.core.mesh_round import sharded_fold  # noqa: E402
from repro.core.treeops import tree_combine  # noqa: E402
from repro.kernels.ops import fold_stacked_tree  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_sim_mesh  # noqa: E402
from repro.models import CNN  # noqa: E402
from repro.sim import RoundEngine, SimConfig  # noqa: E402

# Paper defaults except max_rounds=16 (two plan blocks) and horizon_h=80:
# the default 72 h holds only 15 fedhap rounds here, the 16th ends at
# 75.6 h.
PAPER = SimConfig(strategy="fedhap", stations="two_hap", model_kind="cnn",
                  max_rounds=16, horizon_h=80.0)
SHELLS_800 = SimConfig(strategy="fedhap", stations="grid:3x6",
                       shells="shells:12x40@550+8x40@1200/60",
                       model_kind="cnn", data_shards=4, max_rounds=2)
FOLD_TOL = dict(atol=1e-6, rtol=1e-5)
SEEDS = (0, 1, 2)
# Accuracies are counts over eval_samples. Two differently shaped train
# programs on a TPU v5e (fused vs per-round, or 40 replicas on one chip
# vs 10 on each of four) fold exactly in f32, but their train bursts
# differ in the last bits, and SGD carries the difference through the
# rounds. Fused vs per-round on one chip read 3, 9 and 6 eval samples
# for seeds 0, 1, 2; the bound is twice the largest reading.
CHIP_ACC_SAMPLES = 18
CHIP_ACC_BOUND = CHIP_ACC_SAMPLES / PAPER.eval_samples + 1e-9
KERNEL = "tpu_custom_call"


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


class CompileClock:
    """Backend compile seconds (cache retrievals included) and
    persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.compiles, self.cache_hits


class Checks:
    """Runs every phase; remembers each failure instead of stopping, so
    one call reports them all. ``ok`` is False after any failure."""

    def __init__(self):
        self.failures: list[str] = []

    def check(self, cond: bool, what: str) -> None:
        say(f"{'PASS' if cond else 'FAIL'} {what}")
        if not cond:
            self.failures.append(what)

    def phase(self, name: str, fn, *args) -> None:
        say(f"--- {name}")
        try:
            fn(self, *args)
        except Exception:
            traceback.print_exc()
            self.check(False, f"{name} raised")

    @property
    def ok(self) -> bool:
        return not self.failures


def sync(tree) -> None:
    jax.tree.map(lambda x: x.block_until_ready(), tree)


def max_abs_diff(a, b) -> float:
    return max(float(np.max(np.abs(np.asarray(x, np.float64)
                                   - np.asarray(y, np.float64))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def params_close(a, b) -> bool:
    return all(np.allclose(np.asarray(x), np.asarray(y), **FOLD_TOL)
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def accs_ok(history) -> bool:
    accs = np.array([a for _, _, a in history], np.float64)
    return bool(len(accs)) and bool(np.all(np.isfinite(accs))) and \
        bool(np.all((accs >= 0.0) & (accs <= 1.0)))


def compare_histories(c: Checks, got, want, what: str) -> float:
    """Same rounds and times, accuracies within ``CHIP_ACC_BOUND``.
    Returns the largest accuracy gap in eval samples."""
    c.check(len(got) == len(want), f"{what}: {len(got)} vs {len(want)} "
            f"evaluated rounds")
    c.check(all(tg == tw and eg == ew for (tg, eg, _), (tw, ew, _)
                in zip(got, want)), f"{what}: identical round times")
    dacc = max((abs(ag - aw) for (_, _, ag), (_, _, aw) in zip(got, want)),
               default=0.0)
    samples = dacc * PAPER.eval_samples
    c.check(dacc <= CHIP_ACC_BOUND, f"{what}: max |d acc| {dacc!r} "
            f"({samples!r} eval samples) <= {CHIP_ACC_SAMPLES} samples")
    return samples


def report_gaps(what: str, gaps: list[float]) -> None:
    say(f"{what}: max |d acc| per seed {dict(zip(SEEDS, gaps))!r} eval "
        f"samples; bound {CHIP_ACC_SAMPLES}")


def timed_run(clock: CompileClock, eng: RoundEngine, **kw):
    c0, n0, h0 = clock.snapshot()
    t0 = time.perf_counter()
    res = eng.run(**kw)
    sync(res.params)
    wall = time.perf_counter() - t0
    c1, n1, h1 = clock.snapshot()
    return res, wall, c1 - c0, n1 - n0, h1 - h0


# ------------------------------------------------------------- one chip
def phase_paper(c: Checks, clock: CompileClock) -> None:
    t0 = time.perf_counter()
    eng = RoundEngine(PAPER)
    say(f"set-up (engine build, host) {time.perf_counter() - t0!r} s; "
        f"{eng.n_sats} satellites, "
        f"{eng.trainer.model.count_params()} params")
    res, wall, comp, n_comp, hits = timed_run(clock, eng)
    say(f"rounds run {res.rounds}")
    say(f"accuracy history {[a for _, _, a in res.history]!r}")
    say(f"cold run wall {wall!r} s; backend compile {comp!r} s over "
        f"{n_comp} programs; persistent-cache hits {hits}")
    c.check(res.rounds == PAPER.max_rounds,
            f"{res.rounds} rounds == {PAPER.max_rounds}")
    c.check(accs_ok(res.history), "accuracies finite and in [0, 1]")

    again, steady, _, n_comp2, _ = timed_run(clock, eng)
    say(f"steady run wall {steady!r} s for {again.rounds} rounds "
        f"({again.rounds / steady!r} rounds/s)")
    c.check(n_comp2 == 0, f"steady run compiled {n_comp2} programs")

    # The compiled round program, taken from the executor's own cache.
    ex = eng.executor
    (key, fn), = [(k, f) for k, f in ex._jit.items() if k[0] == "round"]
    K, S, n_steps = key[1:]
    need = n_steps * PAPER.batch_size
    spec = jax.ShapeDtypeStruct
    params, data = jax.tree.map(lambda x: spec(x.shape, x.dtype),
                                (res.params, ex._data))
    text = fn.lower(
        params, data, spec((K, S, need), np.int32), spec((K, S), np.float32),
        spec((K,), np.bool_), spec((K,), np.bool_)).compile().as_text()
    c.check(KERNEL in text,
            f"compiled round program {key} contains {KERNEL}")

    gaps = []
    for seed in SEEDS:
        cfg = dataclasses.replace(PAPER, seed=seed)
        if seed != PAPER.seed:
            res, *_ = timed_run(clock, RoundEngine(cfg))
        ref, ref_wall, *_ = timed_run(clock, RoundEngine(cfg), fused=False)
        say(f"seed {seed}: per-round reference wall {ref_wall!r} s; "
            f"fused vs per-round final params max |d| "
            f"{max_abs_diff(res.params, ref.params)!r}")
        gaps.append(compare_histories(c, res.history, ref.history,
                                      f"seed {seed}: fused vs per-round"))
    report_gaps("fused vs per-round", gaps)


def fold_inputs():
    """40 stacked replicas of the paper CNN, normalised weights, and
    their float64 numpy fold."""
    base = CNN(CNN_CONFIG).init(jax.random.key(0))
    rng = np.random.default_rng(0)
    S = PAPER.num_orbits * PAPER.sats_per_orbit
    stacked = jax.tree.map(
        lambda x: np.asarray(x)[None] + 0.01 * rng.standard_normal(
            (S,) + x.shape).astype(np.float32), base)
    w = rng.random(S).astype(np.float32)
    w /= w.sum()
    want = jax.tree.map(
        lambda x: np.einsum("s,s...->...", w.astype(np.float64),
                            x.astype(np.float64)), stacked)
    return stacked, w, want


def phase_fold(c: Checks) -> None:
    """Both fold backends against a float64 numpy fold."""
    stacked, w, want = fold_inputs()
    dev = jax.device_put((stacked, w))

    pallas = jax.jit(fold_stacked_tree).lower(*dev).compile()
    c.check(KERNEL in pallas.as_text(), f"fold program contains {KERNEL}")
    got = pallas(*dev)
    say(f"Pallas fold vs float64 max |d| {max_abs_diff(got, want)!r}")
    c.check(params_close(got, want),
            "Pallas fold matches float64 (atol=1e-6, rtol=1e-5)")

    got = jax.jit(tree_combine)(*dev)
    say(f"einsum fold vs float64 max |d| {max_abs_diff(got, want)!r}")
    c.check(params_close(got, want),
            "einsum fold matches float64 (atol=1e-6, rtol=1e-5)")


# ---------------------------------------------------------- four chips
def phase_shells(c: Checks, clock: CompileClock) -> None:
    t0 = time.perf_counter()
    eng = RoundEngine(SHELLS_800)
    say(f"set-up (engine build, host) {time.perf_counter() - t0!r} s; "
        f"{eng.n_sats} satellites over {SHELLS_800.data_shards} shards")
    res, wall, comp, n_comp, _ = timed_run(clock, eng)
    say(f"rounds run {res.rounds}; accuracy history "
        f"{[a for _, _, a in res.history]!r}")
    say(f"run wall {wall!r} s; backend compile {comp!r} s over "
        f"{n_comp} programs")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    say(f"peak HBM per chip (bytes) {peaks!r}")
    c.check(None not in peaks, "peak HBM reported for every chip")
    c.check(res.rounds == SHELLS_800.max_rounds,
            f"{res.rounds} rounds == {SHELLS_800.max_rounds}")
    c.check(accs_ok(res.history), "accuracies finite and in [0, 1]")


def phase_sharded_fold(c: Checks) -> None:
    """The sharded fold (per-chip Pallas fold, then one psum) against
    the one-chip Pallas fold and float64, on the same replicas."""
    stacked, w, want = fold_inputs()
    mesh = make_sim_mesh(4)
    sharded = jax.jit(jax.shard_map(
        lambda st, wt: sharded_fold(st, wt, ("data",)), mesh=mesh,
        in_specs=(P("data"), P("data")), out_specs=P(), check_vma=False))
    compiled = sharded.lower(stacked, w).compile()
    c.check(KERNEL in compiled.as_text(),
            f"sharded fold program contains {KERNEL}")
    got4 = compiled(stacked, w)
    got1 = jax.jit(fold_stacked_tree)(*jax.device_put((stacked, w)))
    say(f"sharded fold vs one-chip fold max |d| "
        f"{max_abs_diff(got4, got1)!r}; vs float64 "
        f"{max_abs_diff(got4, want)!r}")
    c.check(params_close(got4, got1) and params_close(got4, want),
            "sharded fold matches the one-chip fold and float64 "
            "(atol=1e-6, rtol=1e-5)")


def phase_sharded_paper(c: Checks, clock: CompileClock) -> None:
    gaps = []
    for seed in SEEDS:
        cfg = dataclasses.replace(PAPER, seed=seed)
        eng1 = RoundEngine(cfg)
        eng4 = RoundEngine(dataclasses.replace(cfg, data_shards=4))
        one, wall1, *_ = timed_run(clock, eng1)
        four, wall4, *_ = timed_run(clock, eng4)
        say(f"seed {seed}: one-device wall {wall1!r} s; data_shards=4 "
            f"wall {wall4!r} s; final params max |d| "
            f"{max_abs_diff(four.params, one.params)!r}")
        gaps.append(compare_histories(c, four.history, one.history,
                                      f"seed {seed}: data_shards=4 vs "
                                      f"one device"))
        if seed == SEEDS[0]:
            engines = eng1, eng4
    report_gaps("data_shards=4 vs one device", gaps)
    one_round_in_chip_shape(c, *engines)


def one_round_in_chip_shape(c: Checks, eng1: RoundEngine,
                            eng4: RoundEngine) -> None:
    """One planned round through the sharded program against the same
    round on one device, trained in the per-chip shape: each chip's
    satellite slice run alone, the partial folds summed in float64.
    Both use the K-round programs the runs above compiled, with only the
    first round valid."""
    ex1, ex4 = eng1.executor, eng4.executor
    K, S, D = PAPER.plan_block, eng1.n_sats, ex4.n_shards
    s_loc = S // D
    rng = np.random.default_rng(42)
    idx = rng.integers(0, len(eng1.fd.images),
                       (K, S, PAPER.local_steps * PAPER.batch_size))
    mu = rng.random((K, S)).astype(np.float32)
    mu /= mu.sum(axis=1, keepdims=True)
    flags = np.zeros(K, bool), np.arange(K) < 1

    def one_round(ex, cols):
        p, _ = ex.run_block(eng1.trainer.init(0), idx[:, cols],
                            mu[:, cols], *flags)
        return jax.tree.map(lambda x: np.asarray(x, np.float64), p)

    four = one_round(ex4, slice(None))
    one = one_round(ex1, slice(None))
    parts = [one_round(ex1, slice(j * s_loc, (j + 1) * s_loc))
             for j in range(D)]
    chip_shape = jax.tree.map(lambda *xs: sum(xs), *parts)
    say(f"one round: data_shards=4 vs one device in per-chip shape "
        f"({D} x {s_loc} replicas) max |d| "
        f"{max_abs_diff(four, chip_shape)!r}; one device {S} vs "
        f"{D} x {s_loc} replicas (train shape) "
        f"{max_abs_diff(one, chip_shape)!r}; data_shards=4 vs one device "
        f"{max_abs_diff(four, one)!r}")
    c.check(params_close(four, chip_shape),
            "one round: data_shards=4 matches one device in per-chip "
            "shape (atol=1e-6, rtol=1e-5)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (jax.devices()[0] is "
              f"{devices[0].platform}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    dev = devices[0]
    say(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {use_compile_cache()}")
    clock = CompileClock()
    checks = Checks()
    if args.chips == 1:
        checks.phase("fold backends vs float64", phase_fold)
        checks.phase("paper-5x8 fedhap, 16 rounds", phase_paper, clock)
    else:
        checks.phase("800 satellites, data_shards=4", phase_shells, clock)
        checks.phase("sharded fold vs one-chip fold", phase_sharded_fold)
        checks.phase("paper-5x8 data_shards=4 vs one device",
                     phase_sharded_paper, clock)
    say(f"total backend compile {clock.seconds!r} s over {clock.compiles} "
        f"programs; persistent-cache hits {clock.cache_hits}")
    if not checks.ok:
        say(f"{len(checks.failures)} check(s) failed: {checks.failures}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
