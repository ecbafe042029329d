"""Spans, scopes and counters (``repro.obs``): the totals' arithmetic,
a run's counters against what its plan tensors' shapes say was
dispatched and uploaded, the device scopes in the compiled programs, and
outputs that the instrumentation leaves bit for bit unchanged."""
import contextlib
import re

import jax
import numpy as np
import pytest

from repro import obs
from repro.sim import RoundEngine, SimConfig

# The 2x2 shell over the paper's two HAPs, one local step.
TINY = dict(num_orbits=2, sats_per_orbit=2, num_samples=2000,
            eval_samples=100, local_steps=1, stations="two_hap",
            eval_every_rounds=1)
CASES = {"fedhap": dict(strategy="fedhap", max_rounds=10, plan_block=8,
                        horizon_h=24.0),
         "fedsat": dict(strategy="fedsat", max_rounds=3, horizon_h=6.0)}
METHOD = {"fedhap": "run_block", "fedsat": "fedsat_event"}


def engine(name: str) -> RoundEngine:
    return RoundEngine(SimConfig(**TINY, **CASES[name]))


def record_calls(eng: RoundEngine, method: str) -> list:
    """Wrap the executor's ``method``; the returned list collects each
    call's positional arguments."""
    calls = []
    orig = getattr(eng.executor, method)

    def wrapped(*args):
        calls.append(args)
        return orig(*args)

    setattr(eng.executor, method, wrapped)
    return calls


@pytest.fixture(scope="module", params=sorted(CASES))
def traced(request):
    """One instrumented run of each tiny case: the engine, the recorded
    executor calls and the result."""
    eng = engine(request.param)
    calls = record_calls(eng, METHOD[request.param])
    return request.param, eng, calls, eng.run()


# ------------------------------------------------------------- totals
def test_span_nesting_totals_and_run_reset():
    before = obs.totals()
    with obs.run() as run:
        with obs.span("t.outer"):
            with obs.span("t.inner"):
                obs.count("t.items", 2)
            obs.count("t.items", 3)
    assert run["t.outer.calls"] == run["t.inner.calls"] == 1
    assert run["t.items"] == 5
    assert run["t.outer.seconds"] >= run["t.inner.seconds"] > 0
    assert obs.last_run() == run
    after = obs.totals()
    for name, v in run.items():
        assert after[name] - before.get(name, 0) == pytest.approx(v)
    # A new run starts empty; outside a run only the process counts.
    with obs.run() as second:
        pass
    assert second == {} and obs.last_run() == {}
    obs.count("t.items", 1)
    assert obs.last_run() == {}
    assert obs.totals()["t.items"] == after["t.items"] + 1


def test_nested_run_and_failed_run():
    with obs.run() as outer:
        obs.count("t.a")
        with obs.run() as inner:
            obs.count("t.b")
        obs.count("t.a")
    assert outer == {"t.a": 2} and inner == {"t.b": 1}
    assert obs.last_run() == outer
    with pytest.raises(ValueError):
        with obs.run():
            with obs.span("t.raises"):
                raise ValueError
    # The failed run is not the last run; its span still closed.
    assert obs.last_run() == outer
    assert obs.totals()["t.raises.calls"] >= 1


def test_upload_counts_bytes_as_sent():
    with obs.run() as run:
        a = obs.upload(np.arange(6, dtype=np.int64), np.int32)
        b = obs.upload(np.ones((2, 3)))          # float64 goes as f32
        c = obs.upload(np.array([True, False]), bool)
        obs.fetch(a)
    assert (a.dtype, b.dtype, c.dtype) == (np.int32, np.float32, np.bool_)
    assert run["exec.upload_bytes"] == 6 * 4 + 6 * 4 + 2
    assert run["exec.sync.calls"] == 1


# ---------------------------------------------------------- counters
def expected_fedhap(eng, calls):
    """Each block uploads idx (int32), mu (f32) and two bool flags."""
    up = sum(idx.size * 4 + mu.size * 4 + do_eval.size + valid.size
             for _, idx, mu, do_eval, valid in calls)
    return len(calls), up


def expected_fedsat(eng, calls):
    """One ``broadcast_rows``, then per tick the tick program (the
    visited orbits padded to a power of two) and a host-path eval that
    sends the eval set."""
    eval_bytes = eng.eval_images.astype(np.float32).nbytes \
        + len(eng.eval_labels) * 4
    up = 0
    for _, _, visited, idx, lam, rhos in calls:
        v = len(visited)
        vp = 1 << int(np.ceil(np.log2(v)))
        k, need = lam.shape[1], idx.shape[1]
        up += vp * k * need * 4 + vp * 4 + vp * k * 4 + vp * 4 + vp
        up += eval_bytes
    return 1 + 2 * len(calls), up


def test_run_counters_match_plan_tensor_shapes(traced):
    name, eng, calls, res = traced
    assert calls
    dispatches, up = {"fedhap": expected_fedhap,
                      "fedsat": expected_fedsat}[name](eng, calls)
    c = res.counters
    assert c["exec.dispatches"] == dispatches
    assert c["exec.upload_bytes"] == up
    assert c["sim.plan.calls"] == len(calls)
    updates = (sum(int(np.asarray(v).sum()) for *_, v in calls)
               if name == "fedhap" else
               sum(len(args[2]) for args in calls))
    assert c["updates"] == updates == res.history[-1][1]
    # Every program was built in this run, once.
    assert c["exec.build.calls"] == len(eng.executor._jit)
    assert c.get("exec.dispatch.calls", 0) + c["exec.build.calls"] \
        == c["exec.dispatches"] - c.get("sim.eval.calls", 0)


def test_second_run_dispatches_built_programs(traced):
    name, eng, calls, res = traced
    again = eng.run()
    assert "exec.build.calls" not in again.counters
    for key in ("exec.dispatches", "exec.upload_bytes", "updates",
                "sim.plan.calls"):
        assert again.counters[key] == res.counters[key]
    assert obs.last_run() == again.counters


# ------------------------------------------------------------ scopes
def scopes(text: str) -> set:
    return {s for s in ("train", "fold", "eval")
            if re.search(rf'op_name="[^"]*\b{s}/', text)}


def spec(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        tree)


def test_compiled_programs_carry_scopes(traced):
    name, eng, calls, res = traced
    ex = eng.executor
    S = jax.ShapeDtypeStruct
    params = spec(res.params)
    if name == "fedhap":
        (key, fn), = [(k, f) for k, f in ex._jit.items()
                      if k[0] == "round"]
        K, n_sats, n_steps = key[1:]
        need = n_steps * eng.cfg.batch_size
        args = (params, spec(ex._data), S((K, n_sats, need), np.int32),
                S((K, n_sats), np.float32), S((K,), np.bool_),
                S((K,), np.bool_))
        want = {"train", "fold", "eval"}
    else:
        key, fn = [(k, f) for k, f in ex._jit.items()
                   if k[0] == "fedsat"][0]
        vp, k, n_steps = key[1:]
        need = n_steps * eng.cfg.batch_size
        bases = jax.tree.map(
            lambda x: S((eng.cfg.num_orbits,) + x.shape, x.dtype), params)
        args = (params, bases, spec(ex._data_local), S((vp,), np.int32),
                S((vp * k, need), np.int32), S((vp, k), np.float32),
                S((vp,), np.float32), S((vp,), np.bool_))
        want = {"train", "fold"}          # FedSat evaluates on the host
    assert scopes(fn.lower(*args).compile().as_text()) == want


def test_trainer_programs_carry_scopes():
    eng = engine("fedhap")
    tr = eng.trainer
    params = tr.init(0)
    x = jax.ShapeDtypeStruct((2, 28, 28), np.float32)
    y = jax.ShapeDtypeStruct((2,), np.int32)
    text = tr._eval.lower(params, x, y).compile().as_text()
    assert scopes(text) == {"eval"}
    stacked = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct((2,) + p.shape, p.dtype), params)
    xs = jax.ShapeDtypeStruct((2, 1, 2, 28, 28), np.float32)
    ys = jax.ShapeDtypeStruct((2, 1, 2), np.int32)
    text = tr._train_many.lower(stacked, xs, ys).compile().as_text()
    assert scopes(text) == {"train"}


# ------------------------------------------------- no numeric effect
def test_outputs_bitwise_equal_without_spans_and_scopes(traced,
                                                        monkeypatch):
    name, _, _, res = traced
    monkeypatch.setattr(obs, "span", lambda name: contextlib.nullcontext())
    monkeypatch.setattr(obs, "scope", lambda name: contextlib.nullcontext())
    plain = engine(name).run()
    assert plain.history == res.history
    for a, b in zip(jax.tree.leaves(plain.params),
                    jax.tree.leaves(res.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert "sim.plan.calls" not in plain.counters
    assert plain.counters["exec.upload_bytes"] \
        == res.counters["exec.upload_bytes"]


def test_resumed_run_counts_only_its_own_updates(tmp_path):
    cfg = dict(TINY, **CASES["fedhap"])
    half = RoundEngine(SimConfig(**dict(cfg, max_rounds=2))).run(
        checkpoint_dir=tmp_path, checkpoint_every=1)
    res = RoundEngine(SimConfig(**dict(cfg, max_rounds=4))).run(
        checkpoint_dir=tmp_path, resume=True, checkpoint_every=1)
    assert half.counters["updates"] == half.history[-1][1] == 2
    assert res.counters["updates"] == res.history[-1][1] - 2 == 2
