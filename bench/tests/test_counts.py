"""FLOP and byte counts against XLA's count and the program's shapes."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import counts
from conftest import BENCH, load

MODEL = load(BENCH / "configs" / "paper-5x8.json")["model"]


def test_cnn_forward_flops_match_xla_cost_analysis():
    from repro.configs.paper_cnn import CONFIG
    from repro.models import CNN
    model = CNN(CONFIG)
    p = model.init(jax.random.key(0))
    x = jnp.zeros((1, 28, 28), jnp.float32)
    cost = jax.jit(model.forward).lower(p, x).compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    assert counts.forward_flops(MODEL) == pytest.approx(24.55e6, rel=1e-3)
    # XLA counts a convolution's taps on the image only, and also biases,
    # ReLUs and pooling: within 1% above the taps on the image.
    on_image = sum(counts.cnn_layer_flops(MODEL, valid_only=True).values())
    assert on_image <= cost["flops"] <= 1.01 * on_image


def test_layer_flops():
    f = counts.cnn_layer_flops(MODEL)
    assert f == {"conv1": 1_254_400, "conv2": 20_070_400,
                 "fc1": 3_211_264, "fc2": 10_240}
    assert counts.train_flops(MODEL) == 3 * sum(f.values())


def test_param_count_matches_program():
    from repro.configs.paper_cnn import CONFIG
    from repro.models import CNN
    assert counts.cnn_params(MODEL) == MODEL["params"] == 1_663_370
    assert CNN(CONFIG).count_params() == MODEL["params"]


@pytest.mark.parametrize("rows", [40, 196])
def test_fold_bytes(rows):
    p = MODEL["params"]
    want = rows * p * 4 + p * 4 + rows * 4
    assert counts.fold_bytes(rows, p) == want
    # 40 rows: the 266 MB a paper round's fold streams.
    if rows == 40:
        assert counts.fold_bytes(rows, p) == pytest.approx(2.728e8,
                                                           rel=1e-3)


def test_window_flops():
    sim = {"local_steps": 54, "batch_size": 32, "eval_samples": 4000}
    work = {"trained": 40, "evals": 1}
    got = counts.window_flops(MODEL, sim, work)
    fwd = counts.forward_flops(MODEL)
    assert got == 40 * 54 * 32 * 3 * fwd + 4000 * fwd
    # One paper round: about 5.09 TFLOP of training and 0.098 of eval.
    assert got == pytest.approx(5.09e12 + 0.098e12, rel=2e-3)
