"""Fused execute phase: Pallas-backed fold equivalence, plan-ahead
driver vs per-round reference histories, the single-transfer evaluate,
and the batched grid-time index."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_cnn import CONFIG as CNN_CONFIG
from repro.configs.paper_mlp import CONFIG as MLP_CONFIG
from repro.core.treeops import tree_combine
from repro.kernels.ops import fedagg_tree, fold_stacked_tree
from repro.models import CNN, MLP
from repro.sim import RoundEngine, SimConfig
from repro.sim.executor import tree_combine_many

QUICK = dict(model_kind="mlp", num_samples=1500, eval_samples=300,
             local_steps=2, horizon_h=36.0, time_step_s=120.0,
             max_rounds=4)

# Every registered strategy with a station scenario it supports.
SCENARIOS = [
    ("fedhap", "one_hap"),
    ("fedisl", "gs"),
    ("fedisl_ideal", "meo"),
    ("fedsat", "gs_np"),
    ("fedspace", "gs"),
    ("fedsink", "haps:2"),
    ("fedhap_async", "haps:2"),
    ("fedhap_buffered", "haps:2"),
]


def _stacked_model_tree(model, n_replicas=5, seed=0):
    """A realistically-shaped stacked param tree: n perturbed inits."""
    params = model.init(jax.random.key(seed))
    keys = jax.random.split(jax.random.key(seed + 1), n_replicas)
    return jax.tree.map(
        lambda x: jnp.stack([
            x + 0.01 * jax.random.normal(k, x.shape) for k in keys]),
        params)


class TestFedaggTreeEquivalence:
    """`fedagg_tree` (Pallas kernel, interpret mode on CPU) vs the
    einsum reference `tree_combine` on REAL model pytrees — the two
    backends of the megastep's fold. FMA/reduction-order differences
    between the kernel's mul+sum and the einsum's dot make exact
    bitwise equality backend-dependent, so equivalence is asserted to
    within a few f32 ULPs of the aggregated values (absolute 1e-6 on
    O(0.1) parameters, measured max ~3e-8)."""

    TOL = dict(atol=1e-6, rtol=1e-5)

    @pytest.mark.parametrize("model", [MLP(MLP_CONFIG), CNN(CNN_CONFIG)],
                             ids=["mlp", "cnn"])
    def test_matches_einsum_on_model_trees(self, model):
        stacked = _stacked_model_tree(model)
        w = jax.random.uniform(jax.random.key(7), (5,), jnp.float32)
        w = w / w.sum()
        got = fedagg_tree(stacked, w)
        want = tree_combine(stacked, w)
        for g, x in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(x),
                                       **self.TOL)

    def test_fold_dispatcher_backends(self):
        stacked = _stacked_model_tree(MLP(MLP_CONFIG))
        w = jnp.asarray([0.5, 0.2, 0.1, 0.1, 0.1], jnp.float32)
        via_kernel = fold_stacked_tree(stacked, w, use_pallas=True)
        via_einsum = fold_stacked_tree(stacked, w, use_pallas=False)
        for a, b in zip(jax.tree.leaves(via_kernel),
                        jax.tree.leaves(via_einsum)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       **self.TOL)

    def test_combine_many_matches_per_round_folds(self):
        stacked = _stacked_model_tree(MLP(MLP_CONFIG))
        mus = jax.random.uniform(jax.random.key(3), (4, 5), jnp.float32)
        batched = tree_combine_many(stacked, mus)
        for k in range(4):
            one = tree_combine(stacked, mus[k])
            for a, b in zip(jax.tree.leaves(batched),
                            jax.tree.leaves(one)):
                np.testing.assert_allclose(np.asarray(a[k]),
                                           np.asarray(b), atol=1e-6)


class TestFusedVsPerRoundHistories:
    @pytest.mark.parametrize("strategy,stations", SCENARIOS)
    def test_histories_allclose(self, strategy, stations):
        cfg = dict(strategy=strategy, stations=stations, **QUICK)
        ref = RoundEngine(SimConfig(**cfg)).run(fused=False)
        fus = RoundEngine(SimConfig(**cfg)).run(fused=True)
        assert fus.rounds == ref.rounds, \
            f"{strategy}: {fus.rounds} fused events vs {ref.rounds}"
        assert fus.sim_hours == ref.sim_hours
        for (t_r, e_r, a_r), (t_f, e_f, a_f) in zip(ref.history,
                                                    fus.history):
            assert t_f == t_r and e_f == e_r
            np.testing.assert_allclose(a_f, a_r, rtol=1e-4, atol=1e-5)

    def test_target_accuracy_truncates_identically(self):
        """A mid-block target hit must stop the fused run at the same
        event, time, and accuracy as the per-round reference."""
        cfg = dict(strategy="fedhap", stations="one_hap",
                   target_accuracy=0.05, **QUICK)   # hit on first eval
        ref = RoundEngine(SimConfig(**cfg)).run(fused=False)
        fus = RoundEngine(SimConfig(**cfg)).run(fused=True)
        assert ref.rounds == 1 and fus.rounds == 1
        assert fus.history == ref.history
        assert fus.sim_hours == ref.sim_hours

    def test_eval_every_rounds_respected(self):
        cfg = dict(strategy="fedhap", stations="one_hap",
                   eval_every_rounds=2, **QUICK)
        ref = RoundEngine(SimConfig(**cfg)).run(fused=False)
        fus = RoundEngine(SimConfig(**cfg)).run(fused=True)
        assert [e for _, e, _ in fus.history] == \
            [e for _, e, _ in ref.history]
        assert len(fus.history) == len(ref.history) < QUICK["max_rounds"]

    def test_round_program_takes_dataset_as_argument(self):
        """A closed-over dataset is baked into the executable as a
        constant, which makes the paper round program too large for a
        persistent compile cache; it must arrive as an argument."""
        eng = RoundEngine(SimConfig(strategy="fedhap", stations="one_hap",
                                    **QUICK))
        res = eng.run()
        ex = eng.executor
        (key, fn), = [(k, f) for k, f in ex._jit.items()
                      if k[0] == "round"]
        K, S, n_steps = key[1:]
        spec = jax.ShapeDtypeStruct
        params, data = jax.tree.map(lambda x: spec(x.shape, x.dtype),
                                    (res.params, ex._data))
        need = n_steps * eng.cfg.batch_size
        compiled = fn.lower(
            params, data, spec((K, S, need), np.int32),
            spec((K, S), np.float32), spec((K,), np.bool_),
            spec((K,), np.bool_)).compile()
        data_bytes = sum(x.nbytes for x in ex._data)
        assert compiled.memory_analysis().argument_size_in_bytes \
            >= data_bytes


class TestEvaluateSingleTransfer:
    @pytest.mark.parametrize("model", [MLP(MLP_CONFIG), CNN(CNN_CONFIG)],
                             ids=["mlp", "cnn"])
    @pytest.mark.parametrize("n", [100, 2048, 3000, 4096])
    def test_bit_equal_to_per_chunk_reference(self, model, n):
        from repro.data import make_digits_dataset
        from repro.sim.trainer import LocalTrainer
        imgs, labs = make_digits_dataset(4096, seed=0)
        imgs, labs = imgs[:n], labs[:n]
        tr = LocalTrainer(model)
        params = tr.init(0)
        batch = 2048
        want = sum(                       # the old per-chunk float() path
            float(tr._eval(params, jnp.asarray(imgs[i:i + batch]),
                           jnp.asarray(labs[i:i + batch])))
            * len(imgs[i:i + batch]) for i in range(0, n, batch)) / n
        assert tr.evaluate(params, imgs, labs) == want


class TestBatchedTidx:
    def test_matches_scalar_reference(self):
        eng = RoundEngine(SimConfig(strategy="fedhap", stations="one_hap",
                                    **QUICK))
        rng = np.random.default_rng(0)
        ts = np.concatenate([
            rng.uniform(0, eng.horizon_s, 200),
            [0.0, eng.horizon_s, eng.horizon_s * 2],   # clamp past grid
        ])
        batched = eng.tidx(ts)
        scalar = np.array([
            min(int(t / eng.cfg.time_step_s), eng.vis.shape[2] - 1)
            for t in ts])
        np.testing.assert_array_equal(batched, scalar)
        assert eng._tidx(ts[0]) == batched[0]
        assert batched.dtype == np.int64
