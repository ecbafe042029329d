"""FLOP and byte counts against XLA's count and the program's shapes."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import counts
import parts
from conftest import BENCH, MLP_MODEL, load

MODEL = load(BENCH / "configs" / "paper-5x8.json")["model"]
PAYLOAD = parts.payload("cnn")


def test_cnn_forward_flops_match_xla_cost_analysis():
    from repro.configs.paper_cnn import CONFIG
    from repro.models import CNN
    model = CNN(CONFIG)
    p = model.init(jax.random.key(0))
    x = jnp.zeros((1, 28, 28), jnp.float32)
    cost = jax.jit(model.forward).lower(p, x).compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    assert PAYLOAD.forward_flops(MODEL) == pytest.approx(24.55e6, rel=1e-3)
    # XLA counts a convolution's taps on the image only, and also biases,
    # ReLUs and pooling: within 1% above the taps on the image.
    on_image = sum(PAYLOAD.layer_flops(MODEL, valid_only=True).values())
    assert on_image <= cost["flops"] <= 1.01 * on_image


def test_layer_flops():
    f = PAYLOAD.layer_flops(MODEL)
    assert f == {"conv1": 1_254_400, "conv2": 20_070_400,
                 "fc1": 3_211_264, "fc2": 10_240}
    assert PAYLOAD.train_flops(MODEL) == 3 * sum(f.values())


def _program_model(kind: str):
    from repro.configs.paper_cnn import CONFIG as CNN_CONFIG
    from repro.configs.paper_mlp import CONFIG as MLP_CONFIG
    from repro.models import CNN, MLP
    return CNN(CNN_CONFIG) if kind == "cnn" else MLP(MLP_CONFIG)


@pytest.mark.parametrize("model,count", [(MODEL, 1_663_370),
                                         (MLP_MODEL, 199_210)],
                         ids=["cnn", "mlp"])
def test_param_count_matches_program(model, count):
    program = _program_model(model["kind"])
    assert parts.payload(model["kind"]).params(model) == model["params"] \
        == count
    assert program.count_params() == model["params"]


def test_mlp_flops_match_xla_cost_analysis():
    """The 2NN's dense layers; XLA also counts biases and ReLUs, within
    1% above."""
    program = _program_model("mlp")
    p = program.init(jax.random.key(0))
    x = jnp.zeros((1, 28, 28), jnp.float32)
    cost = jax.jit(program.forward).lower(p, x).compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    mlp = parts.payload("mlp")
    assert mlp.forward_flops(MLP_MODEL) == 2 * (784 * 200 + 200 * 200
                                                + 200 * 10)
    assert mlp.train_flops(MLP_MODEL) == 3 * mlp.forward_flops(MLP_MODEL)
    assert (mlp.forward_flops(MLP_MODEL) <= cost["flops"]
            <= 1.01 * mlp.forward_flops(MLP_MODEL))


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
    fwd = PAYLOAD.forward_flops(MODEL)
    assert got == 40 * 54 * 32 * 3 * fwd + 4000 * fwd
    # One paper round: about 5.09 TFLOP of training and 0.098 of eval.
    assert got == pytest.approx(5.09e12 + 0.098e12, rel=2e-3)
